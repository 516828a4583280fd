"""Decoder-only LM generic over the 10-arch config schema.

Layers are grouped by the architecture's *pattern period* P
(lcm of the hybrid attention period and the MoE period; P=1 for
homogeneous stacks) and scanned over ``n_layers / P`` repeats with the P
positions unrolled inside the scan body — so HLO stays compact (one body
per distinct layer structure) for every architecture including jamba's
1-attention-per-8 interleave.

Entry points:
  init_params(cfg, key)        real parameters (smoke tests, examples)
  abstract_params(cfg)         ShapeDtypeStructs (dry-run, no allocation)
  forward / forward_with_aux   logits (train / prefill-style full pass)
  init_cache / prefill / decode_step   serving path with KV/SSM caches
"""

from __future__ import annotations

import math
from functools import partial
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from .config import ModelConfig
from .. import pspec
from . import layers as L
from .layers import init_norm, norm
from .mamba import init_mamba, init_mamba_cache, mamba_block
from .moe import init_moe, moe_block

__all__ = ["pattern_period", "init_params", "abstract_params", "forward",
           "forward_with_aux", "init_cache", "prefill", "decode_step"]

# parameters kept in float32 regardless of compute dtype (numerics-critical)
_F32_LEAVES = ("A_log", "D", "dt_bias", "router")


@jax.custom_vjp
def _grad_to_compute_dtype(x):
    """Identity whose backward casts the cotangent to the primal dtype.

    f32-accumulating einsums (norm statistics, attention scores) make their
    VJPs produce float32 cotangents; without a cast at the layer boundary
    the entire backward residual chain — and every backward dot and its
    FSDP gathers — runs in f32, doubling collective and HBM traffic
    (§Perf iteration 4).  Megatron keeps inter-layer grads in bf16 for the
    same reason; dW still accumulates in f32 inside the optimizer.
    """
    return x


def _gtc_fwd(x):
    return x, jnp.zeros((0,), x.dtype)  # residual carries only the dtype


def _gtc_bwd(res, g):
    return (g.astype(res.dtype),)


_grad_to_compute_dtype.defvjp(_gtc_fwd, _gtc_bwd)


def _dtype(name: str):
    return {"float32": jnp.float32, "bfloat16": jnp.bfloat16}[name]


def pattern_period(cfg: ModelConfig) -> int:
    p = cfg.attn_period
    if cfg.moe is not None:
        p = math.lcm(p, cfg.moe.every)
    assert cfg.n_layers % p == 0, (cfg.n_layers, p)
    return p


def cast_tree(tree, dtype):
    """Cast weight leaves to the compute dtype, keeping numerics-critical
    leaves (SSM decay, router) in float32."""

    def cast(path, a):
        name = str(path[-1]) if path else ""
        if any(k in name for k in _F32_LEAVES):
            return a
        if a.dtype in (jnp.float32, jnp.bfloat16):
            return a.astype(dtype)
        return a

    return jax.tree_util.tree_map_with_path(
        lambda p, a: cast([getattr(k, "key", getattr(k, "idx", "")) for k in p], a),
        tree)


# ---------------------------------------------------------------------------
# parameter construction
# ---------------------------------------------------------------------------


def _init_layer(key, cfg: ModelConfig, kind: str, is_moe: bool, dtype) -> Dict:
    k1, k2 = jax.random.split(key)
    p: Dict[str, Any] = {"ln1": init_norm(cfg.norm, cfg.d_model, dtype),
                         "ln2": init_norm(cfg.norm, cfg.d_model, dtype)}
    if kind == "attn":
        if cfg.attention.kind == "mla":
            p["mix"] = L.init_mla(k1, cfg.attention, cfg.d_model, dtype)
        else:
            p["mix"] = L.init_attention(k1, cfg.attention, cfg.d_model, dtype)
    else:
        p["mix"] = init_mamba(k1, cfg.ssm, cfg.d_model, dtype)
    if is_moe:
        p["ffn"] = init_moe(k2, cfg.moe, cfg.d_model, dtype)
    elif cfg.d_ff > 0:
        gated = cfg.activation == "silu"
        p["ffn"] = L.init_mlp(k2, cfg.d_model, cfg.d_ff, dtype, gated=gated)
    else:
        del p["ln2"]  # pure-mamba layer (falcon-mamba): mixer only
    return p


def init_params(cfg: ModelConfig, key: jax.Array) -> Dict:
    dtype = _dtype(cfg.param_dtype)
    P = pattern_period(cfg)
    R = cfg.n_layers // P
    kinds = cfg.layer_kinds()
    moes = cfg.moe_layers()
    k_emb, k_unemb, k_blocks, k_extra = jax.random.split(key, 4)

    blocks = []
    for pos in range(P):
        keys = jax.random.split(jax.random.fold_in(k_blocks, pos), R)
        stacked = [_init_layer(keys[r], cfg, kinds[pos], moes[pos], dtype)
                   for r in range(R)]
        blocks.append(jax.tree.map(lambda *xs: jnp.stack(xs), *stacked))

    params: Dict[str, Any] = {
        "embed": jax.random.normal(k_emb, (cfg.vocab_size, cfg.d_model), dtype)
        * (cfg.d_model ** -0.5),
        "blocks": tuple(blocks),
        "final_norm": init_norm(cfg.norm, cfg.d_model, dtype),
    }
    if not cfg.tie_embeddings:
        params["unembed"] = jax.random.normal(
            k_unemb, (cfg.d_model, cfg.vocab_size), dtype) * (cfg.d_model ** -0.5)
    if cfg.n_patches > 0:  # VLM stub: projection of precomputed patch embeds
        params["patch_proj"] = jax.random.normal(
            k_extra, (cfg.d_model, cfg.d_model), dtype) * (cfg.d_model ** -0.5)
    return params


def abstract_params(cfg: ModelConfig) -> Dict:
    """ShapeDtypeStruct pytree — the dry-run path, no allocation."""
    return jax.eval_shape(partial(init_params, cfg), jax.random.key(0))


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------


def _layer_apply(cfg: ModelConfig, kind: str, is_moe: bool, lp: Dict,
                 x: jnp.ndarray, positions: jnp.ndarray,
                 cache: Optional[Dict], impl: str, chunk: int,
                 ) -> Tuple[jnp.ndarray, Optional[Dict], jnp.ndarray]:
    aux = jnp.zeros((), jnp.float32)
    # barrier: stops XLA hoisting the per-layer bf16->f32 norm upcast out of
    # the scan loop (which would materialize an f32 copy of the entire
    # (L, B, S, d) carry stack — observed on XLA:CPU); its transpose puts
    # the same barrier on the cotangent in the backward scan
    x = jax.lax.optimization_barrier(x)
    # Megatron-SP discipline (training): the residual is sequence-sharded
    # between layers; gather the *activations* (tokens x d, small at
    # microbatched train shapes) at layer entry so the TP matmuls never
    # force XLA to all-gather full weight matrices (d x d_ff) instead.
    # Serving keeps h sequence-sharded: at 32k prefill the activation is
    # the big operand, and weights gather once per layer anyway
    # (§Perf iterations 3 and p1).
    h_spec = (None if cache is None else "sp")
    h = pspec.shard(norm(cfg.norm, x, lp["ln1"]), "batch", h_spec, None)
    if kind == "attn":
        fn = L.mla_block if cfg.attention.kind == "mla" else L.attention_block
        mixed, new_cache = fn(lp["mix"], h, cfg.attention, positions=positions,
                              causal=True, cache=cache, impl=impl, chunk=chunk)
    else:
        mixed, new_cache = mamba_block(lp["mix"], h, cfg.ssm, cache=cache,
                                       impl=cfg.ssm_impl)
    x = _grad_to_compute_dtype(pspec.shard(x + mixed, "batch", "sp", None))
    if "ffn" not in lp:          # pure-mamba layer (falcon-mamba)
        return x, new_cache, aux
    h = pspec.shard(norm(cfg.norm, x, lp["ln2"]), "batch", h_spec, None)
    if is_moe:
        ff, aux = moe_block(lp["ffn"], h, cfg.moe, activation=cfg.activation)
    else:
        ff = L.mlp_block(lp["ffn"], h, cfg.activation)
    return (_grad_to_compute_dtype(pspec.shard(x + ff, "batch", "sp", None)),
            new_cache, aux)


def _embed(params: Dict, cfg: ModelConfig, tokens: jnp.ndarray,
           patches: Optional[jnp.ndarray], dtype) -> jnp.ndarray:
    x = params["embed"][tokens].astype(dtype)
    if cfg.n_patches > 0 and patches is not None:
        px = (patches.astype(dtype) @ params["patch_proj"].astype(dtype))
        x = jnp.concatenate([px, x], axis=1)
    # pin the residual-stream layout: the vocab-sharded gather would
    # otherwise leave x replicated (see repro.pspec docstring)
    return pspec.shard(x, "batch", "sp", None)


def _unembed(params: Dict, x: jnp.ndarray, dtype) -> jnp.ndarray:
    unemb = params.get("unembed")
    if unemb is None:
        logits = x @ params["embed"].T.astype(dtype)
    else:
        logits = x @ unemb.astype(dtype)
    return pspec.shard(logits, "batch", None, "tp")


def forward_with_aux(params: Dict, cfg: ModelConfig, tokens: jnp.ndarray,
                     patches: Optional[jnp.ndarray] = None,
                     impl: Optional[str] = None, chunk: int = 1024,
                     remat: bool = True) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """tokens: (B, S_text); VLM: patches (B, n_patches, d) prepended.
    Returns (logits (B, S_total, V), moe aux loss)."""
    impl = impl or cfg.attention_impl
    dtype = _dtype(cfg.compute_dtype)
    x = _embed(params, cfg, tokens, patches, dtype)
    b, s, _ = x.shape
    positions = jnp.arange(s)[None, :]
    P = pattern_period(cfg)
    kinds = cfg.layer_kinds()[:P]
    moes = cfg.moe_layers()[:P]

    G = max(1, cfg.remat_group)
    R = cfg.n_layers // P
    assert R % G == 0, (R, G)

    def body(carry, rep_params):
        x, aux = carry
        for g in range(G):
            gp = jax.tree.map(lambda a: a[g], rep_params) if G > 1 else rep_params
            for pos in range(P):
                x, _, a = _layer_apply(cfg, kinds[pos], moes[pos],
                                       gp[pos], x, positions, None,
                                       impl, chunk)
                aux = aux + a
        return (x, aux), ()

    if remat:
        # hierarchical rematerialization: the scan saves the residual every
        # remat_group repeats; backward recomputes the whole group from it.
        # nothing_saveable (vs dots_saveable) keeps chunked-attention score
        # blocks out of memory — the flash-style memory plan.
        body = jax.checkpoint(body,
                              policy=jax.checkpoint_policies.nothing_saveable)
    # cast the stacked params ONCE, outside the scan: FSDP all-gathers then
    # move bf16, not f32 master weights (2x less gather traffic and no
    # full-f32 weight materialization inside the layer body)
    blocks_c = cast_tree(params["blocks"], dtype)
    if G > 1:  # group the leading repeat dim: (R, ...) -> (R/G, G, ...)
        blocks_c = jax.tree.map(
            lambda a: a.reshape((R // G, G) + a.shape[1:]), blocks_c)
    (x, aux), _ = jax.lax.scan(body, (x, jnp.zeros((), jnp.float32)),
                               blocks_c)
    x = norm(cfg.norm, x, params["final_norm"])
    return _unembed(params, x, dtype), aux


def forward(params, cfg, tokens, patches=None, impl=None,
            chunk: int = 1024, remat: bool = True) -> jnp.ndarray:
    return forward_with_aux(params, cfg, tokens, patches, impl, chunk,
                            remat)[0]


# ---------------------------------------------------------------------------
# serving: cache init / prefill / decode
# ---------------------------------------------------------------------------


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


def _layer_cache(cfg: ModelConfig, kind: str, batch: int, max_len: int, dtype):
    a = cfg.attention
    if kind == "attn":
        if a.kind == "mla":
            return {"c_kv": jnp.zeros((batch, max_len, a.kv_lora_rank), dtype),
                    "k_rope": jnp.zeros((batch, max_len, a.qk_rope_head_dim), dtype),
                    "pos": jnp.zeros((), jnp.int32)}
        t = max_len if a.window == 0 else min(max_len, _round_up(a.window, 128))
        return {"k": jnp.zeros((batch, t, a.n_kv_heads, a.head_dim), dtype),
                "v": jnp.zeros((batch, t, a.n_kv_heads, a.head_dim), dtype),
                "kpos": jnp.full((t,), -1, jnp.int32),
                "pos": jnp.zeros((), jnp.int32)}
    return init_mamba_cache(cfg.ssm, cfg.d_model, batch, dtype)


def init_cache(cfg: ModelConfig, batch: int, max_len: int) -> Tuple:
    """Stacked caches mirroring the block structure: tuple over pattern
    positions, each a pytree with leading repeat dim R."""
    dtype = _dtype(cfg.compute_dtype)
    P = pattern_period(cfg)
    R = cfg.n_layers // P
    kinds = cfg.layer_kinds()[:P]
    caches = []
    for pos in range(P):
        c = _layer_cache(cfg, kinds[pos], batch, max_len, dtype)
        caches.append(jax.tree.map(
            lambda x: jnp.broadcast_to(x[None], (R,) + x.shape).copy(), c))
    return tuple(caches)


def prefill(params: Dict, cfg: ModelConfig, tokens: jnp.ndarray,
            cache: Tuple, patches: Optional[jnp.ndarray] = None,
            impl: str = "chunked", chunk: int = 1024
            ) -> Tuple[jnp.ndarray, Tuple]:
    """Run the prompt through the model, filling caches.  Returns
    (last-position logits (B, 1, V), cache)."""
    dtype = _dtype(cfg.compute_dtype)
    x = _embed(params, cfg, tokens, patches, dtype)
    b, s, _ = x.shape
    positions = jnp.arange(s)[None, :]
    P = pattern_period(cfg)
    kinds = cfg.layer_kinds()[:P]
    moes = cfg.moe_layers()[:P]

    def body(x, inp):
        rep_params, rep_cache = inp
        new_caches = []
        for pos in range(P):
            x, nc, _ = _layer_apply(cfg, kinds[pos], moes[pos],
                                    rep_params[pos], x, positions,
                                    rep_cache[pos], impl, chunk)
            new_caches.append(nc if nc is not None else rep_cache[pos])
        return x, tuple(new_caches)

    blocks_c = cast_tree(params["blocks"], dtype)
    x, new_cache = jax.lax.scan(body, x, (blocks_c, cache))
    x = norm(cfg.norm, x[:, -1:], params["final_norm"])
    return _unembed(params, x, dtype), new_cache


def decode_step(params: Dict, cfg: ModelConfig, token: jnp.ndarray,
                cache: Tuple) -> Tuple[jnp.ndarray, Tuple]:
    """One decode step.  token: (B, 1) -> logits (B, 1, V), updated cache."""
    dtype = _dtype(cfg.compute_dtype)
    x = pspec.shard(params["embed"][token].astype(dtype), "batch", None, None)
    P = pattern_period(cfg)
    kinds = cfg.layer_kinds()[:P]
    moes = cfg.moe_layers()[:P]
    pos0 = _find_pos(cache)
    positions = pos0 + jnp.zeros((1, 1), jnp.int32)

    def body(x, inp):
        rep_params, rep_cache = inp
        new_caches = []
        for pos in range(P):
            x, nc, _ = _layer_apply(cfg, kinds[pos], moes[pos],
                                    rep_params[pos], x, positions,
                                    rep_cache[pos], "dense", 1024)
            new_caches.append(nc if nc is not None else rep_cache[pos])
        return x, tuple(new_caches)

    blocks_c = cast_tree(params["blocks"], dtype)
    x, new_cache = jax.lax.scan(body, x, (blocks_c, cache))
    x = norm(cfg.norm, x, params["final_norm"])
    return _unembed(params, x, dtype), new_cache


def _find_pos(cache: Tuple):
    for c in cache:
        if isinstance(c, dict) and "pos" in c:
            return c["pos"][0]
    return jnp.zeros((), jnp.int32)

"""Serving example: prefill a batch of prompts, then decode with KV/SSM
caches — across three model families (GQA, MLA, SSM).

    PYTHONPATH=src python examples/serve.py
"""

import time

import jax
import jax.numpy as jnp

from repro.compile_cache import enable_compile_cache
from repro.configs import get_smoke_config
from repro.models import get_model


def generate(arch: str, prompt_len: int = 16, gen_len: int = 24,
             batch: int = 4):
    cfg = get_smoke_config(arch)
    model = get_model(cfg)
    params = model.init_params(jax.random.key(0))
    prompts = jax.random.randint(jax.random.key(1), (batch, prompt_len), 0,
                                 cfg.vocab_size)
    batch_in = {"tokens": prompts}
    if cfg.n_patches:
        batch_in["patches"] = jnp.zeros((batch, cfg.n_patches, cfg.d_model))
    if cfg.enc_dec is not None:
        batch_in["frames"] = jnp.zeros(
            (batch, cfg.enc_dec.encoder_len, cfg.d_model))

    cache = model.init_cache(batch, prompt_len + gen_len)
    t0 = time.perf_counter()
    logits, cache = model.prefill(params, batch_in, cache)
    t_prefill = time.perf_counter() - t0

    decode = jax.jit(model.decode_step)
    tok = jnp.argmax(logits[:, -1], axis=-1)[:, None].astype(jnp.int32)
    out = [tok]
    t0 = time.perf_counter()
    for _ in range(gen_len - 1):
        logits, cache = decode(params, tok, cache)
        tok = jnp.argmax(logits[:, -1], axis=-1)[:, None].astype(jnp.int32)
        out.append(tok)
    jax.block_until_ready(tok)
    t_decode = time.perf_counter() - t0
    toks = jnp.concatenate(out, axis=1)
    print(f"{arch:22s} prefill {prompt_len} toks: {t_prefill*1e3:7.1f} ms | "
          f"decode {gen_len} toks: {t_decode*1e3/gen_len:6.1f} ms/tok | "
          f"sample: {toks[0, :8].tolist()}")


def main():
    for arch in ("olmo-1b", "minicpm3-4b", "falcon-mamba-7b",
                 "jamba-v0.1-52b", "whisper-small"):
        generate(arch)


if __name__ == "__main__":
    enable_compile_cache()
    main()

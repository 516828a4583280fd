"""Compile-ahead checks for a TPU v5e that is described, not attached.

The TPU compiler refuses what the Pallas interpreter accepts (unaligned
lane slices, VMEM overruns), so the kernels and the packed evaluator are
compiled here for one chip of a described ``v5e:2x2``.  Nothing runs:
these tests say that the programs compile, not what they compute.  The
topology is described inside a fixture, so only the worker that runs
this file loads the TPU library, and a host that cannot describe it
skips."""

import functools
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.aidg.explorer import Explorer, default_scenarios
from repro.kernels.flash_attention import flash_attention_pallas
from repro.kernels.maxplus import maxplus_matmul_pallas, maxplus_matvec_pallas
from repro.kernels.selective_scan import selective_scan_pallas
from repro.kernels.systolic_gemm import systolic_gemm_pallas

V5E_HBM_BYTES = 16 * 2**30


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a program compiled for a described chip cannot be read back from
    # the persistent cache without one: keep it out of the cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compile(fn, one_chip, *shapes):
    args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip)
            for s, dt in shapes]
    return jax.jit(fn).lower(*args).compile()


F32, BF16 = jnp.float32, jnp.bfloat16

KERNELS = {
    # the blocked evaluator's block-128 shapes, and a 4x4-block matrix
    "maxplus_matmul": (maxplus_matmul_pallas,
                       [((512, 512), F32), ((512, 512), F32)]),
    "maxplus_matmul_column": (maxplus_matmul_pallas,
                              [((128, 128), F32), ((128, 1), F32)]),
    "maxplus_matvec": (maxplus_matvec_pallas,
                       [((512, 512), F32), ((512,), F32)]),
    "systolic_gemm": (systolic_gemm_pallas,
                      [((512, 512), BF16), ((512, 512), BF16)]),
    "flash_attention": (functools.partial(flash_attention_pallas,
                                          causal=True),
                        [((8, 1024, 64), BF16)] * 3),
    # falcon_mamba_7b's d_inner = 8192 with d_state = 16
    "selective_scan": (selective_scan_pallas,
                       [((1, 256, 8192), F32), ((1, 256, 8192), F32),
                        ((1, 256, 16), F32), ((1, 256, 16), F32),
                        ((8192, 16), F32), ((8192,), F32)]),
}


@pytest.mark.parametrize("name", sorted(KERNELS))
def test_kernel_compiles_for_v5e(name, one_chip):
    fn, shapes = KERNELS[name]
    compiled = _compile(fn, one_chip, *shapes)
    assert "tpu_custom_call" in compiled.as_text(), (
        f"{name}: no Pallas kernel in the compiled program")


def test_packed_evaluator_compiles_for_v5e(one_chip):
    """The production dispatch (``PackedMatrix._full_fn``) for one
    operator cell at batch 64, with its memory inside one chip."""
    cells = [s for s in default_scenarios() if s.name == "systolic/gemm"]
    ex = Explorer(scenarios=cells)
    fn = ex.packed_matrix()._full_fn()
    arg = jax.ShapeDtypeStruct((64, ex.space.n), jnp.float32,
                               sharding=one_chip)
    compiled = fn.lower(arg).compile()
    mem = compiled.memory_analysis()
    used = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes + mem.generated_code_size_in_bytes)
    assert 0 < used < V5E_HBM_BYTES


# an instruction of compiled HLO text: name, shape dims, layout, opcode
_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%?([^\s=]+) = \w+\[([\d,]*)\]"
                    r"\{([\d,]*)[^}]*\}\S* ([\w-]+)\(")
_COMPUTATION = re.compile(r"^(?:ENTRY\s+)?%?([\w.-]+) \(.*\{\s*$")
_CALLEE = re.compile(r"(?:body|condition|calls|to_apply|branch_computations)"
                     r"=\{?((?:%?[\w.-]+(?:,\s*)?)+)")


def _instructions(text):
    """``(name, dims, layout, opcode, in_loop)`` of every array instruction
    of a compiled module, ``in_loop`` when a while body reaches it."""
    comps, cur = {}, None
    for line in text.splitlines():
        m = _COMPUTATION.match(line)
        if m:
            cur = comps.setdefault(m.group(1), [])
        elif cur is not None:
            cur.append(line)
    calls = {c: {n.strip().lstrip("%") for k in _CALLEE.findall("\n".join(ls))
                 for n in k.split(",")} for c, ls in comps.items()}
    todo = [b for ls in comps.values() for line in ls
            for b in re.findall(r"body=%?([\w.-]+)", line)]
    in_loop = set()
    while todo:
        c = todo.pop()
        if c not in in_loop:
            in_loop.add(c)
            todo.extend(calls.get(c, ()))
    for c, ls in comps.items():
        for line in ls:
            m = _INSTR.match(line)
            if m:
                name, dims, layout, op = m.groups()
                yield (name, [int(d) for d in dims.split(",") if d],
                       [int(d) for d in layout.split(",") if d], op,
                       c in in_loop)


def test_packed_scans_keep_the_candidates_on_the_lanes(one_chip):
    """The packed evaluator's bucket scans carry the candidate batch as
    the minor-most (lane) dimension, so every per-step state write is a
    dense slice or a select.  Two gamma cells make one 2-row bucket with
    a 2-slot queue, the shape of the ``net28`` benchmark's slowest gamma
    bucket; with the candidates on a leading axis the relaxation's write
    touched a partial tile per candidate and the queue's slot update was
    a per-candidate scatter inside the scan loop."""
    B = 256
    cells = [s for s in default_scenarios()
             if s.name in ("gamma/gemm", "gamma/scan")]
    ex = Explorer(scenarios=cells)
    assert ex.packed_matrix().stats()["buckets"] == 1
    arg = jax.ShapeDtypeStruct((B, ex.space.n), jnp.float32,
                               sharding=one_chip)
    text = ex.packed_matrix()._full_fn().lower(arg).compile().as_text()
    batched = [i for i in _instructions(text) if B in i[1]]
    writes = [i for i in batched if i[3] == "dynamic-update-slice"]
    assert any(in_loop for *_, in_loop in writes), "no scan state write"
    for name, dims, layout, _, _ in writes:
        assert dims[layout[0]] == B, (name, dims, layout)
    scattered = [(name, dims) for name, dims, _, op, in_loop in batched
                 if op == "scatter" and in_loop]
    assert not scattered, scattered

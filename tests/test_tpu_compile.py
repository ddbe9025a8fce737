"""Compile guard for the TPU v5e: the four Pallas kernels at the widths of
the models that use them, and one server's pooled round, prefill and
row-indexed prefill steps of OLMo-1B at its published widths, compiled
for a described (not attached) v5e.  Each asserts that Mosaic kernels
(``tpu_custom_call``) are in the compiled program; two more assert that
the XLA round step and row prefill step write their donated pool in
place.  Nothing runs: a pass says the chip's compiler
accepts the program, not that its results are right.

The topology is described inside a module fixture, never at import: only
one process may load the TPU library, and every test worker imports this
file.
"""
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.kernels import decode_attention, flash_attention, ssd, wkv6
from repro.kernels.decode_attention import ops as decode_ops
from repro.kernels.flash_attention import ops as flash_ops
from repro.kernels.ssd import ops as ssd_ops
from repro.kernels.wkv6 import ops as wkv6_ops
from repro.models import init_params_shapes
from repro.models.model import block_param_range
from repro.serving.kv_cache import (make_pool_prefill_step,
                                    make_pool_round_step,
                                    make_pool_row_prefill_step,
                                    new_state_pool_tree)

B, T = 4, 1024  # decode rows and cache length
N_ROWS, W, BLOCKS = 8, 8, 4  # one server of the chip smoke's fleet


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # the persistent cache cannot read back a compile for a described chip
    jax.config.update("jax_enable_compilation_cache", False)
    return SingleDeviceSharding(topo.devices[0])


def _compiled_text(one_chip, fn, *shapes, rows=0):
    """``fn`` compiled for the chip; ``rows > 0`` vmaps it over that many
    rows, the pooled serving steps' idiom."""
    lead = (rows,) if rows else ()
    args = [jax.ShapeDtypeStruct(lead + s, dt, sharding=one_chip)
            for s, dt in shapes]
    fn = jax.vmap(fn) if rows else fn
    return jax.jit(fn).lower(*args).compile().as_text()


BF16, F32, I32 = jnp.bfloat16, jnp.float32, jnp.int32

KERNELS = {
    # OLMo-1B: 16 MHA heads of 128
    "decode_olmo": (
        lambda q, k, v, p: decode_attention(q, k, v, p, interpret=False),
        ((B, 1, 16, 128), BF16), ((B, T, 16, 128), BF16),
        ((B, T, 16, 128), BF16), ((B,), I32)),
    "flash_olmo": (
        lambda q, k, v: flash_attention(q, k, v, causal=True,
                                        interpret=False),
        ((1, 512, 16, 128), BF16), ((1, 512, 16, 128), BF16),
        ((1, 512, 16, 128), BF16)),
    # BLOOM: 112 heads of 128 with ALiBi slopes
    "decode_alibi_bloom": (
        lambda q, k, v, p, sl: decode_attention(q, k, v, p, slopes=sl,
                                                interpret=False),
        ((B, 1, 112, 128), BF16), ((B, T, 112, 128), BF16),
        ((B, T, 112, 128), BF16), ((B,), I32), ((112,), F32)),
    # rwkv6-7b: 64 heads of 64
    "wkv6_rwkv6_7b": (
        lambda r, k, v, lw, u: wkv6(r, k, v, lw, u, interpret=False),
        ((1, 128, 64, 64), F32), ((1, 128, 64, 64), F32),
        ((1, 128, 64, 64), F32), ((1, 128, 64, 64), F32), ((64, 64), F32)),
    # zamba2-7b: 112 heads of 64, state 64
    "ssd_zamba2_7b": (
        lambda x, b, c, dt, a, d: ssd(x, b, c, dt, a, d, interpret=False),
        ((1, 256, 112, 64), F32), ((1, 256, 64), F32), ((1, 256, 64), F32),
        ((1, 256, 112), F32), ((112,), F32), ((112,), F32)),
}


@pytest.mark.parametrize("rows", [0, N_ROWS], ids=["direct", "vmapped"])
@pytest.mark.parametrize("name", sorted(KERNELS))
def test_kernel_compiles_for_v5e(one_chip, name, rows):
    fn, *shapes = KERNELS[name]
    assert "tpu_custom_call" in _compiled_text(one_chip, fn, *shapes,
                                               rows=rows)


def _olmo_server(one_chip):
    """Shapes of one OLMo-1B server's pooled-step operands on the chip:
    (cfg, kinds, params, pool, spec)."""
    cfg = get_config("olmo_1b")
    kinds = ("decoder",) * BLOCKS
    place = lambda tree: jax.tree.map(  # noqa: E731
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one_chip),
        tree)
    params = place(jax.eval_shape(  # one run of same-kind blocks
        lambda p: (block_param_range(p, cfg, "decoder", 0, BLOCKS),),
        init_params_shapes(cfg)[0]))
    pool = place(jax.eval_shape(lambda: (new_state_pool_tree(
        cfg, "decoder", BLOCKS, N_ROWS, T),)))
    spec = lambda shape, dt: jax.ShapeDtypeStruct(  # noqa: E731
        shape, dt, sharding=one_chip)
    return cfg, kinds, params, pool, spec


def _lower_round(one_chip, backend):
    cfg, kinds, params, pool, spec = _olmo_server(one_chip)
    mask = spec((BLOCKS, N_ROWS), jnp.bool_)
    lids, dummy = spec((BLOCKS,), I32), spec((1, 1, 1), F32)
    return pool, make_pool_round_step(cfg, kinds, backend).lower(
        params, None, pool, spec((W, 1, cfg.d_model), BF16), spec((W,), I32),
        dummy, spec((W,), I32), spec((N_ROWS,), I32), spec((W,), I32), mask,
        lids)


def _lower_row_prefill(one_chip, backend, bucket):
    cfg, kinds, params, pool, spec = _olmo_server(one_chip)
    lids, dummy = spec((BLOCKS,), I32), spec((1, 1, 1), F32)
    return pool, make_pool_row_prefill_step(cfg, kinds, backend).lower(
        params, None, pool, spec((1, bucket, cfg.d_model), BF16), dummy,
        dummy, spec((), I32), spec((BLOCKS, 1), jnp.bool_), lids, 0, "all")


@pytest.mark.parametrize("step", ["round", "prefill", "row_prefill"])
def test_olmo_pooled_step_compiles_for_v5e(one_chip, monkeypatch, step):
    """One server's pooled step with ``backend="pallas"``: four OLMo-1B
    blocks, 8 rows of 1024-token bf16 cache, round width 8, and a
    512-token prefill bucket (over every row, or over one indexed row)."""
    # the kernels pick interpret mode from the default backend, which is
    # the CPU here: compile them for the described chip instead
    for mod in (decode_ops, flash_ops, ssd_ops, wkv6_ops):
        monkeypatch.setattr(mod, "default_interpret", lambda: False)
    if step == "round":
        _, lowered = _lower_round(one_chip, "pallas")
    elif step == "row_prefill":
        _, lowered = _lower_row_prefill(one_chip, "pallas", 512)
    else:
        cfg, kinds, params, pool, spec = _olmo_server(one_chip)
        mask = spec((BLOCKS, N_ROWS), jnp.bool_)
        lids, dummy = spec((BLOCKS,), I32), spec((1, 1, 1), F32)
        fn = make_pool_prefill_step(cfg, kinds, "pallas")
        lowered = fn.lower(params, None, pool,
                           spec((N_ROWS, 512, cfg.d_model), BF16), dummy,
                           dummy, mask, lids, 0, "all")
    assert "tpu_custom_call" in lowered.compile().as_text()


def _assert_in_place(pool, lowered, temp_share):
    """The compiled step's scratch memory is under ``temp_share`` of the
    pool, and it makes no copy of a whole pool leaf."""
    compiled = lowered.compile()
    leaves = jax.tree.leaves(pool)
    pool_bytes = sum(x.size * x.dtype.itemsize for x in leaves)
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert temp < temp_share * pool_bytes, (temp, pool_bytes)
    shape = ",".join(map(str, leaves[0].shape))
    assert not re.search(rf"= bf16\[{shape}\]\S* copy\(",
                         compiled.as_text())


def test_olmo_round_step_writes_pool_in_place(one_chip):
    """The round step on the XLA backend writes the donated pool in place:
    its scratch memory is a small fraction of the pool, and no copy of the
    whole pool is made."""
    _assert_in_place(*_lower_round(one_chip, "xla"), 0.1)


def test_olmo_row_prefill_step_writes_pool_in_place(one_chip):
    """The row prefill step on the XLA backend, at the 1024-token bucket,
    writes its one row into the donated pool in place: no copy of the
    whole pool, and scratch for one row's activations (68 MB against the
    N-row step's 1.18 GB over 8 rows), under half the 268 MB pool."""
    _assert_in_place(*_lower_row_prefill(one_chip, "xla", 1024), 0.5)

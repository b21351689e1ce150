"""Ahead-of-time compiles for a described TPU v5e (no chip attached).

The TPU compiler is installed here and compiles for a topology that is
described, not attached; it refuses what interpret mode accepts (tiling,
VMEM, shape casts Mosaic cannot lay out).  Each case compiles one kernel of
the serving path, or the flat step of a benchmark model, at real widths
on one chip of a ``v5e:2x2`` topology and asserts the Pallas kernel is in
the compiled program (``tpu_custom_call``), or that the donated flat step
never moves the KV pool.

The topology is described inside a module-scoped fixture, never at import:
only one process may load the TPU library, so every xdist worker must
collect the same tests and only the one running this file may load it.
"""

import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import RunConfig, get_config
from repro.configs.base import ShapeSpec
from repro.configs.registry import ARCHS
from repro.core import make_layout, presets
from repro.core.linear import prepack_params
from repro.kernels.mmt4d.ops import mmt4d
from repro.kernels.pack.ops import pack
from repro.kernels.ragged_attn.kernel import ragged_attention_kernel_call
from repro.kernels.unpack.ops import unpack
from repro.models.attention import KVPageLayout
from repro.models.model import build_model

# serving geometry of chip_smoke.py: 4 slots, max_len 1024, 16-token pages
SLOTS, PAGE_TOKENS, MAX_PAGES = 4, 16, 64
NUM_PAGES = 1 + SLOTS * MAX_PAGES


def _head_geometries():
    """(hq, hkv, dh) of every config with attention layers, first arch
    holding each geometry."""
    geos = {}
    for arch in ARCHS:
        cfg = get_config(arch)
        if "attn" in cfg.layer_types:
            geos.setdefault((cfg.n_heads, cfg.n_kv_heads, cfg.d_head), arch)
    return [(arch, *g) for g, arch in geos.items()]


# (arch, hq, hkv, dh, W, slots, MP): every head geometry at chip_smoke.py's
# serving size, then each benchmark cell's own (olmo-1b: 20 slots x 128
# pages, smollm2-135m: 128 x 64) at its decode width and its widest
RAGGED_CASES = ([(arch, hq, hkv, dh, 64, SLOTS, MAX_PAGES)
                 for arch, hq, hkv, dh in _head_geometries()]
                + [("smollm2-135m", 9, 3, 64, 4, SLOTS, MAX_PAGES)]
                + [("olmo-1b", 16, 16, 128, w, 20, 128) for w in (32, 512)]
                + [("smollm2-135m", 9, 3, 64, w, 128, 64) for w in (16, 512)])


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip cannot be read back from the cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _spec(one_chip, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)


def _compiled_text(one_chip, fn, *shapes):
    return jax.jit(fn).lower(*[_spec(one_chip, s, d) for s, d in shapes]) \
        .compile().as_text()


def _ragged_id(arch, hq, hkv, dh, w, slots, mp):
    size = "" if (slots, mp) == (SLOTS, MAX_PAGES) else f"-{slots}x{mp}"
    return f"{arch}-{hq}/{hkv}/{dh}-W{w}{size}"


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bf16", "f32"])
@pytest.mark.parametrize("arch,hq,hkv,dh,w,slots,mp", RAGGED_CASES,
                         ids=[_ragged_id(*c) for c in RAGGED_CASES])
def test_ragged_kernel_compiles(one_chip, arch, hq, hkv, dh, w, slots, mp,
                                dtype):
    """The kernel at a pool of two layers of ``1 + slots * MP`` pages: its
    page blocks, hand-copied from the pool in HBM, fit VMEM."""
    def call(q, k, v, bt, row_ids, q_pos, layer):
        return ragged_attention_kernel_call(
            q, k, v, layer=layer, block_tables=bt, row_ids=row_ids,
            q_pos=q_pos)

    pages = (2, 1 + slots * mp) + KVPageLayout(hkv, dh,
                                               PAGE_TOKENS).page_shape
    text = _compiled_text(one_chip, call, ((w, hq, dh), dtype),
                          (pages, dtype), (pages, dtype),
                          ((slots, mp), jnp.int32),
                          ((w,), jnp.int32), ((w,), jnp.int32),
                          ((), jnp.int32))
    assert "tpu_custom_call" in text


# smollm2-135m's up projection for a 256-token chunk: [256, 576] x [576, 1536]
M, K, N = 256, 576, 1536


@pytest.fixture(scope="module")
def lay():
    return make_layout("scalable", presets["tpu_v5e"], jnp.bfloat16)


def test_mmt4d_compiles(one_chip, lay):
    text = _compiled_text(
        one_chip, lambda a, b: mmt4d(a, b, interpret=False),
        (lay.packed_lhs_shape(M, K), jnp.bfloat16),
        (lay.packed_rhs_shape(K, N), jnp.bfloat16))
    assert "tpu_custom_call" in text


def test_pack_compiles(one_chip, lay):
    text = _compiled_text(
        one_chip, lambda a: pack(a, lay.m_r, lay.k_r, interpret=False),
        ((M, K), jnp.bfloat16))
    assert "tpu_custom_call" in text


def test_unpack_compiles(one_chip, lay):
    text = _compiled_text(
        one_chip, lambda c: unpack(c, M, N, interpret=False),
        ((M // lay.m_r, N // lay.n_r, lay.m_r, lay.n_r), jnp.bfloat16))
    assert "tpu_custom_call" in text


def test_flat_step_compiles_with_kernel(one_chip, monkeypatch):
    """The whole flat step of smollm2-135m at published widths in bf16, at
    the widest flat width of chip_smoke.py's engine.  The kernel dispatch
    asks ``jax.default_backend()`` at trace time, which sees the CPU here,
    so the test steers it to the TPU branch."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    run = RunConfig(param_dtype="bfloat16", compute_dtype="bfloat16",
                    remat=False)
    model = build_model(get_config("smollm2-135m"), run,
                        ShapeSpec("serve", MAX_PAGES * PAGE_TOKENS, SLOTS,
                                  "decode"))
    place = lambda tree: jax.tree.map(  # noqa: E731
        lambda s: _spec(one_chip, s.shape, s.dtype), tree)
    params = place(jax.eval_shape(lambda: prepack_params(
        model.init(jax.random.PRNGKey(0)), model.ctx)))
    caches = place(jax.eval_shape(
        lambda: model.init_paged_cache(NUM_PAGES, PAGE_TOKENS, SLOTS)))
    w = 256
    i32 = lambda *s: _spec(one_chip, s, jnp.int32)  # noqa: E731
    text = jax.jit(model.flat_decode_step).lower(
        params, caches, i32(1, w), i32(SLOTS, MAX_PAGES), i32(w), i32(w),
        i32(SLOTS)).compile().as_text()
    assert "tpu_custom_call" in text


# the benchmark cells' serving sizes and a decode width each: (slots,
# max_len, W).  At chip_smoke.py's 4 slots the pool is small enough for the
# compiler to prefetch it between memory spaces (copy-start/copy-done), and
# the step's own temp (the tied head's packed embedding) outweighs a layer
# of pool, so neither assertion would say anything there.
POOL_CASES = [("smollm2-135m", 128, 1024, 64), ("olmo-1b", 20, 2048, 32)]


def _pool_moves(text: str, num_pages: int) -> list:
    """Instructions of the compiled program that copy, slice or update a
    tensor holding ``num_pages`` pages (``copy``, ``dynamic-slice`` and
    ``dynamic-update-slice`` instructions, or fusions named for them)."""
    moves = []
    for line in text.splitlines():
        m = re.match(r"\s*(?:ROOT )?%?([\w.-]+) = \(?\w+\[([\d,]*)\]", line)
        if m is None or str(num_pages) not in m.group(2).split(","):
            continue
        op = line.split(" = ", 1)[1]
        if re.search(r"(^|[_.])(copy|dynamic-slice|dynamic-update-slice)"
                     r"([_.]|$)", m.group(1)) or re.search(
                r"\] (copy|copy-start|dynamic-slice|dynamic-update-slice)\(",
                op):
            moves.append(line.strip()[:160])
    return moves


@pytest.mark.parametrize("arch,slots,max_len,w", POOL_CASES,
                         ids=[c[0] for c in POOL_CASES])
def test_flat_step_keeps_pool_in_place(one_chip, monkeypatch, arch, slots,
                                       max_len, w):
    """The donated flat step (``jit_step("flat")``) of each benchmark model
    at its cell's serving size updates the KV pool in place and the kernel
    reads it where it lies: no copy, slice or update of a pool-sized
    tensor, and less temp memory than one layer's K and V pool."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    run = RunConfig(param_dtype="bfloat16", compute_dtype="bfloat16",
                    remat=False)
    cfg = get_config(arch)
    model = build_model(cfg, run, ShapeSpec("serve", max_len, slots,
                                            "decode"))
    max_pages = max_len // PAGE_TOKENS
    num_pages = 1 + slots * max_pages
    place = lambda tree: jax.tree.map(  # noqa: E731
        lambda s: _spec(one_chip, s.shape, s.dtype), tree)
    params = place(jax.eval_shape(lambda: prepack_params(
        model.init(jax.random.PRNGKey(0)), model.ctx)))
    caches = place(jax.eval_shape(
        lambda: model.init_paged_cache(num_pages, PAGE_TOKENS, slots)))
    i32 = lambda *s: _spec(one_chip, s, jnp.int32)  # noqa: E731
    compiled = model.jit_step("flat").lower(
        params, caches, i32(1, w), i32(slots, max_pages), i32(w), i32(w),
        i32(slots)).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    assert _pool_moves(text, num_pages) == []
    layer_pool = 2 * 2 * num_pages * PAGE_TOKENS * cfg.n_kv_heads * cfg.d_head
    assert compiled.memory_analysis().temp_size_in_bytes < layer_pool

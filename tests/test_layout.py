"""Layout algebra: tile functions, policies, padding math (paper §4.2)."""

import jax.numpy as jnp
import pytest
from hypothesis import given, settings, strategies as st

from repro.core.hardware import HardwareSpec, presets, query, sublane_packing
from repro.core.layout import LayoutPolicy, make_layout, ceil_div, round_up


def test_scalable_tiles_follow_hardware():
    """The SVE property: tile sizes are functions of the hardware descriptor."""
    for dtype, pack in [(jnp.float32, 1), (jnp.bfloat16, 2), (jnp.int8, 4)]:
        lay = make_layout("scalable", presets["tpu_v5e"], dtype)
        assert lay.m_r == 8 * pack          # dtype packing (SVE width scaling)
        assert lay.n_r == 128               # VL analogue
        assert lay.k_r == 128               # MXU depth


def test_scalable_tiles_scale_with_vl():
    """Widening the 'vector length' widens the layout (Fig 3 premise)."""
    base = make_layout("scalable", presets["tpu_vl128"], jnp.float32)
    wide = make_layout("scalable", presets["tpu_vl512"], jnp.float32)
    assert wide.n_r == 4 * base.n_r
    assert wide.k_r == 4 * base.k_r


def test_fixed_tiles_ignore_hardware():
    """The NEON property: frozen constants regardless of hardware."""
    a = make_layout("fixed", presets["tpu_vl128"], jnp.bfloat16)
    b = make_layout("fixed", presets["tpu_vl512"], jnp.bfloat16)
    assert (a.m_r, a.n_r, a.k_r) == (b.m_r, b.n_r, b.k_r) == (8, 128, 128)


def test_chain_compatibility():
    lay = make_layout("scalable", presets["tpu_v5e"], jnp.float32)
    assert lay.chain_compatible  # n_r == k_r: free propagation across matmuls


@given(m=st.integers(1, 4096), k=st.integers(1, 4096))
@settings(max_examples=200, deadline=None)
def test_packed_shape_math(m, k):
    lay = make_layout("scalable", presets["tpu_v5e"], jnp.float32)
    mo, ko, mr, kr = lay.packed_lhs_shape(m, k)
    assert mo * mr >= m and (mo - 1) * mr < m
    assert ko * kr >= k and (ko - 1) * kr < k
    assert lay.flops(m, 1, k) == 2 * mo * mr * round_up(k, kr) * lay.n_r


@given(a=st.integers(1, 10**6), b=st.integers(1, 4096))
@settings(max_examples=100, deadline=None)
def test_ceil_div_round_up(a, b):
    assert ceil_div(a, b) * b >= a > (ceil_div(a, b) - 1) * b
    assert round_up(a, b) % b == 0


def test_hardware_query_env(monkeypatch):
    monkeypatch.setenv("REPRO_HW", "tpu_vl256")
    assert query().lanes == 256
    monkeypatch.delenv("REPRO_HW")
    assert query().name in presets


def _fake_tpu(monkeypatch, kind):
    import jax
    from types import SimpleNamespace
    monkeypatch.delenv("REPRO_HW", raising=False)
    monkeypatch.setattr(jax, "devices", lambda *a: [
        SimpleNamespace(platform="tpu", device_kind=kind)])


@pytest.mark.parametrize("kind,preset", [("TPU v5 lite", "tpu_v5e"),
                                         ("TPU v4", "tpu_v4")])
def test_hardware_query_known_tpu_kind(monkeypatch, kind, preset):
    _fake_tpu(monkeypatch, kind)
    assert query() is presets[preset]


def test_hardware_query_unknown_tpu_kind_raises(monkeypatch):
    _fake_tpu(monkeypatch, "TPU v99")
    with pytest.raises(KeyError, match="TPU v99"):
        query()


def test_scaled_spec_controls_only_width():
    """Scaling study premise: compute scales, memory system fixed."""
    hw = presets["tpu_v5e"]
    hw4 = hw.scaled(4)
    assert hw4.flops_bf16 == 4 * hw.flops_bf16
    assert hw4.hbm_bw == hw.hbm_bw and hw4.ici_bw == hw.ici_bw

"""The plain float32 reference (bench/references/dense_decoder.py) against
the program's own ``model.forward``, at a small size on the CPU, both
float32, with the same weights drawn from the seed."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import weights
from bench.references import dense_decoder as ref
from repro.configs.base import ModelConfig, RunConfig, ShapeSpec
from repro.models.model import build_model


def _config(norm, kv):
    return {"bench": {
        "dtype": "float32", "norm_eps": 1e-6,
        "model": {"name": "t", "family": "dense", "n_layers": 3,
                  "d_model": 64, "n_heads": 4, "n_kv_heads": kv,
                  "d_head": 16, "d_ff": 96, "vocab": 300, "norm": norm,
                  "act": "silu", "glu": True, "tie_embeddings": True,
                  "rope": "neox", "rope_theta": 50000.0},
        "serving": {"max_len": 48}}}


@pytest.mark.parametrize("norm,kv", [("rmsnorm", 2), ("layernorm_np", 4)])
def test_reference_matches_model_forward(norm, kv):
    config = _config(norm, kv)
    model = build_model(ModelConfig(**config["bench"]["model"]),
                        RunConfig(param_dtype="float32",
                                  compute_dtype="float32", remat=False),
                        ShapeSpec("t", 48, 2, "prefill"))
    seed = 2 ** 33 + 7
    params = weights.program_params(model, seed, "float32")
    rng = np.random.default_rng(0)
    seqs = [rng.integers(0, 300, n).astype(np.int32) for n in (40, 23)]
    got = [np.asarray(model.forward(params, {"tokens": jnp.asarray(s)[None]})
                      [0][0], np.float32) for s in seqs]
    rows = [(i, p, int(np.argmax(got[i][p])))
            for i, s in enumerate(seqs) for p in range(len(s))]
    r = ref.compare_rows(config, seed, seqs, rows, length=48)
    prog_max = np.array([got[i][p].max() for i, p, _ in rows])
    scale = max(np.abs(g).max() for g in got)
    np.testing.assert_allclose(r.ref_max, prog_max, atol=1e-4 * scale)
    np.testing.assert_allclose(r.ref_served, prog_max, atol=1e-4 * scale)
    assert ref.widest_gap(r.ref_max, r.ref_served) < 1e-4 * scale
    # a token that is not the program's first lies below the best
    second = [(i, p, int(np.argsort(got[i][p])[-2])) for i, p, _ in rows]
    r2 = ref.compare_rows(config, seed, seqs, second, length=48)
    assert np.all(r2.ref_max - r2.ref_served > 0)


def test_reference_weights_are_the_programs():
    config = _config("rmsnorm", 2)
    model = build_model(ModelConfig(**config["bench"]["model"]),
                        RunConfig(param_dtype="bfloat16",
                                  compute_dtype="bfloat16", remat=False),
                        ShapeSpec("t", 48, 2, "prefill"))
    params = weights.program_params(model, 5, "bfloat16")
    for path, shape in (("groups/p0/mixer/wk/w", (64, 32)),
                        ("groups/p0/ffn/wd/w", (96, 64))):
        leaf = params["groups"]["p0"][path.split("/")[2]][
            path.split("/")[3]]["w"]
        for layer in range(3):
            got = weights.reference_leaf(5, path, shape, 3, "bfloat16",
                                         layer=layer)
            np.testing.assert_array_equal(
                np.asarray(leaf[layer], np.float32), np.asarray(got))
    e = weights.reference_leaf(5, "embed/e", (300, 64), 3, "bfloat16")
    np.testing.assert_array_equal(
        np.asarray(params["embed"]["e"], np.float32), np.asarray(e))
    # different seeds give different weights
    assert not np.array_equal(
        np.asarray(e), np.asarray(weights.reference_leaf(
            6, "embed/e", (300, 64), 3, "bfloat16")))


def test_fp8_rounding():
    x = jnp.array([[448.0, 1.0, 0.3, -17.0, 0.0]])
    q = ref.fp8_e4m3(x, -1)
    # scale 1 (the largest is 448): 1.0 exact; 0.3 -> 0.3125 (3 mantissa
    # bits); -17 -> -16 (steps of 2 above 16)
    np.testing.assert_allclose(np.asarray(q),
                               [[448.0, 1.0, 0.3125, -16.0, 0.0]])

"""The plain float32 reference against the program's own full-sequence
forward on the jnp route, at smoke widths on the CPU."""

import jax.numpy as jnp
import numpy as np
import pytest

import harness
import reference
import weights
from conftest import smoke_config


@pytest.mark.parametrize("name", ["olmo-1b.c8.int8-fused",
                                  "granite-8b.c8.bf16"])
def test_reference_matches_the_model(name):
    cfg = smoke_config(name)
    model, layout = harness.build_model(cfg)
    params = weights.make_params(layout, 2**33 + 5)
    toks = np.random.default_rng(0).integers(0, cfg["model"]["vocab"], 24)
    want = np.asarray(model.logits(params, toks[None].astype(np.int32)))[0]
    key = reference.register(cfg["name"], cfg["model"])
    got = np.asarray(reference.forward(key, params, toks.astype(np.int32)))
    np.testing.assert_allclose(got, want, rtol=2e-4,
                               atol=2e-4 * np.abs(want).max())


def test_reference_sees_wrong_permutations():
    cfg = smoke_config("olmo-1b.c8.int8-fused")
    model, layout = harness.build_model(cfg)
    params = weights.make_params(layout, 9)
    toks = np.arange(16, dtype=np.int32)
    want = np.asarray(model.logits(params, toks[None]))[0]
    other = dict(cfg["model"], mpd_seed=1)
    key = reference.register(cfg["name"] + ".seed1", other)
    got = np.asarray(reference.forward(key, params, toks))
    assert np.abs(got - want).max() > 0.1 * np.abs(want).max()


def test_int8_weights_are_exact():
    cfg = smoke_config("olmo-1b.c8.int8-fused")
    _, layout = harness.build_model(cfg)
    params = weights.make_params(layout, 4)
    leaf = params["blocks"][0]["mixer"]["wq"]
    assert leaf["w_q"].dtype == np.int8
    w = reference.packed_f32(leaf)
    q, s = weights.quantize_columns(w)
    np.testing.assert_array_equal(np.asarray(q), np.asarray(leaf["w_q"]))
    np.testing.assert_allclose(np.asarray(s), np.asarray(leaf["w_scale"]),
                               rtol=1e-6)


def test_control_fp8_rounding_is_the_e4m3_cast():
    x = np.random.default_rng(0).normal(0, 60, 20000).astype(np.float32)
    x = np.concatenate([np.clip(x, -448, 448),
                        np.float32([0.0, 2.0 ** -9, 3 * 2.0 ** -10, 448.0,
                                    -300.0, 1.0625, 1.1875])])
    want = x.astype(jnp.float8_e4m3fn).astype(np.float32)
    np.testing.assert_array_equal(np.asarray(reference.round_e4m3(x)), want)

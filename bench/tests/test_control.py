"""The control: the plain reference computed in the next precision below
the configuration's (int4 weights for the int8 configuration, fp8 for the
bf16 one) put in the program's place must read as not correct. Kept here
at smoke size; on the chip it runs at the cell's size through
``bench/calibrate.py limits``."""

import pytest

import gen
import harness
import weights


@pytest.mark.parametrize("which", ["chat", "decode"])
def test_control_fails_the_limit(cpu_cell, which):
    config, mix = cpu_cell[which]
    fmt = config["check"]["control"]
    limits = config["check"]["limits"]
    model, layout = harness.build_model(config)
    for seed in (1, 2, 3):
        params = weights.make_params(layout, seed)
        engine = harness.build_engine(model, params, mix)
        harness.warm(engine, mix)
        reqs = gen.generate(mix, seed, 2.0, config["model"]["vocab"])
        run = harness.drive(engine, reqs, mix, 2.0)
        max_len = mix["engine"]["max_len"]
        sound = harness.compare(config, params, run, max_len, seed)
        ctl = harness.compare(config, params, run, max_len, seed, fmt=fmt)
        assert all(sound[k] <= lim for k, lim in limits.items())
        assert any(ctl[k] > lim for k, lim in limits.items())

"""A whole run at smoke size on the CPU, past the harness's look for a
chip: sound, it is correct; with the timed path broken underneath, it is
not."""

import time
from types import SimpleNamespace

import pytest

import harness


def _cell(config, mix):
    return SimpleNamespace(name="smoke", chips=1, config=config, mix=mix,
                           end_to_end=[], per_layer=[])


def alter_token(engine):
    """A served token changed where the engine produces it."""
    emit, vocab = engine._emit, engine.model.cfg.vocab

    def bad(req, tok):
        emit(req, (tok + 1) % vocab if len(req.generated) == 2 else tok)
    engine._emit = bad


def drop_kv_write(engine):
    """A decode step that hands back the cache state it was given."""
    decode = engine._decode_paged

    def bad(params, caches, *args):
        dev, _, ok = decode(params, caches, *args)
        return dev, caches, ok
    engine._decode_paged = bad


@pytest.mark.parametrize("which", ["chat", "decode"])
@pytest.mark.parametrize("fault", [None, alter_token, drop_kv_write])
def test_correct_only_when_sound(cpu_cell, which, fault, tmp_path):
    config, mix = cpu_cell[which]
    out = harness.cell_run(_cell(config, mix), 2**31 + 77, 2.0, False,
                           time.perf_counter(), out_dir=tmp_path,
                           hooks=fault)
    gap = out["checks"]["max_logit_gap"]
    assert gap["value"] is not None
    if fault is None:
        assert out["correct"] and out["failed"] == 0
        assert gap["value"] <= gap["limit"]
    else:
        assert not out["correct"]
        assert gap["value"] > gap["limit"]

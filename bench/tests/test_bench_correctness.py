"""The comparison that decides ``correct``, on a small cell on the CPU:
sound runs pass, the control (the reference one precision lower) reads
far above them, and a run with the timed path broken underneath comes
out not correct, once for each fault a serving cell can have."""

import numpy as np
import pytest

from bench.tests.tiny import run_tiny, tiny_cell

LIMIT = 0.05    # logits; the tiny model's sound runs read 0 on the CPU


@pytest.fixture(scope="module")
def sound():
    return run_tiny(tiny_cell(LIMIT))[1]


def test_sound_run_is_correct(sound):
    assert sound["correct"] is True
    assert sound["failed"] == 0
    assert sound["reference"]["tokens"] >= 40
    assert sound["checks"]["token_gap_max"]["value"] <= LIMIT


def test_control_reads_far_above_the_program(sound):
    """The reference one precision lower (fp8 activations), put in the
    program's place, goes through the same comparison and limits and
    comes out not correct, where the sound program's served tokens are
    all reference argmaxes."""
    out = run_tiny(tiny_cell(LIMIT), control=True)[1]
    assert out["correct"] is False
    ctl, chk = out["checks"], sound["checks"]
    assert ctl["token_gap_max"]["value"] > LIMIT
    assert ctl["token_gap_max"]["value"] > 0.0 == chk["token_gap_max"]["value"]
    assert ctl["token_gap_mean"]["value"] > 0.0 == \
        chk["token_gap_mean"]["value"]


def test_a_token_altered_where_it_is_produced(monkeypatch):
    import repro.serve.engine as E
    orig = E._apply_decode_tokens

    def altered(disp, toks, retire):
        toks = np.array(toks)
        toks[0] = (toks[0] + 1) % 512          # every token of row 0
        return orig(disp, toks, retire)

    monkeypatch.setattr(E, "_apply_decode_tokens", altered)
    out = run_tiny(tiny_cell(LIMIT))[1]
    assert out["correct"] is False
    assert out["checks"]["token_gap_max"]["value"] > LIMIT


def test_a_decode_step_that_returns_its_cache_unchanged(monkeypatch):
    import repro.models.attention as A
    orig = A._attn_decode_paged

    def no_write(p, x, cfg, layer_cache):
        y, _ = orig(p, x, cfg, layer_cache)
        return y, dict(layer_cache)            # the new token's K/V lost

    monkeypatch.setattr(A, "_attn_decode_paged", no_write)
    out = run_tiny(tiny_cell(LIMIT))[1]
    assert out["correct"] is False

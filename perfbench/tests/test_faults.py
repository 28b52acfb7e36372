"""A run whose timed path is broken underneath comes out not correct.

The harness's look for a chip is skipped (``run.execute``); everything
else of a run is driven as on the chip, at small sizes, with the
system's engine entry points wrapped so that they:

* ``half``: leave out half of each batch and hand back the other half's
  answers in its place;
* ``alter``: alter one answer of each call where it is produced (one
  quantised level of a stream, or one reconstructed pixel).

The cells run on one chip and hold no state across steps, so the other
faults of the list (state left unchanged, exchange between chips left
out) do not exist here.
"""

import numpy as np
import pytest

from perfbench import reference as ref
from perfbench.tests.small import SMALL, execute, small_cell


def _half_list(out):
    n = len(out)
    keep = out[:max(1, n // 2)]
    return [keep[i % len(keep)] for i in range(n)]


def _alter_stream(blob: bytes) -> bytes:
    hdr, levels = ref.parse_dctz(blob)
    levels = levels.copy()
    levels[0, 0, 0, 1] += 1
    return ref.encode_dctz(levels, hdr["quality"], hdr["transform"],
                           (hdr["height"], hdr["width"]))


def _install(monkeypatch, fault: str) -> None:
    from repro.serve import codec_engine as eng
    enc, dec, rt = eng.encode_batch, eng.decode_batch, eng.roundtrip_batch

    def encode_batch(*a, **k):
        out = enc(*a, **k)
        if fault == "half":
            return _half_list(out)
        return out[:-1] + [_alter_stream(out[-1])]

    def decode_batch(*a, **k):
        out = [np.array(r) for r in dec(*a, **k)]
        if fault == "half":
            return _half_list(out)
        out[-1][0, 0] ^= 0x04
        return out

    def roundtrip_batch(*a, **k):
        rec, psnr = rt(*a, **k)
        rec = np.array(rec)
        if fault == "half":
            h = max(1, len(rec) // 2)
            rec[h:] = rec[:h][np.arange(len(rec) - h) % h]
        else:
            rec[-1, 0, 0] ^= 0x04
        return rec, psnr

    monkeypatch.setattr(eng, "encode_batch", encode_batch)
    monkeypatch.setattr(eng, "decode_batch", decode_batch)
    monkeypatch.setattr(eng, "roundtrip_batch", roundtrip_batch)


@pytest.mark.parametrize("fault", ["half", "alter"])
@pytest.mark.parametrize("name", sorted(SMALL))
def test_broken_path_is_not_correct(monkeypatch, name, fault):
    _install(monkeypatch, fault)
    res = execute(small_cell(name))
    assert res["attempted"] > 0
    assert res["correct"] is False, res["checks"]


@pytest.mark.parametrize("name", sorted(SMALL))
def test_sound_path_is_correct(name):
    res = execute(small_cell(name))
    assert res["correct"] is True, res["checks"]

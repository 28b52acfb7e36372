"""Staged NumPy reference for the symbolize kernel (element-exact oracle).

The kernel runs the stages of the host symbolizer's dense per-block
layout (:mod:`repro.core.entropy.dense`: runs, slots, histograms,
compaction) as fixed-shape lane arithmetic on the device; that pass is
its reference, and :func:`symbolize_ref` its stream form.
"""

from __future__ import annotations

from repro.core.entropy.dense import dense_to_stream, symbolize_dense


def symbolize_ref(dc_diff, ac) -> tuple:
    """The staged pipeline end-to-end; element-identical to
    :func:`repro.core.entropy.rle.symbolize_reference`."""
    return dense_to_stream(symbolize_dense(dc_diff, ac))

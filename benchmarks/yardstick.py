"""The fixed arithmetic every cell is judged by: the chip's published peaks
and the guard that refuses a rate no chip could have produced.

Kept under ``benchmarks/`` so that a PR which claims a gain cannot move the
denominator. A device that ``peaks.json`` does not list is an error, never a
default: a utilization against another chip's peak is a wrong number.
"""

import json
from pathlib import Path

PEAKS_FILE = Path(__file__).resolve().parent / "peaks.json"


class UnknownDevice(Exception):
    """``device_kind`` has no row in ``peaks.json``."""


class ImplausibleRate(Exception):
    """A measured rate implies more FLOP/s than the chips' published peak."""


def peaks_for(device_kind):
    table = json.loads(PEAKS_FILE.read_text())
    if device_kind not in table:
        raise UnknownDevice(
            f"device_kind {device_kind!r} is not in {PEAKS_FILE.name} "
            f"(known: {sorted(table)}); add its published peaks with their "
            f"source before measuring on it"
        )
    return table[device_kind]


def mfu(samples_per_s, flops_per_sample, chips, peaks):
    """Model FLOP/s over the chips' peak: the operations the forward and
    backward passes require (recomputed or padded work does not count) at the
    chip's one published matmul peak, whatever precision the cell runs in."""
    return samples_per_s * flops_per_sample / (chips * peaks["flops_per_s"])


def check_plausible(samples_per_s, flops_per_sample, chips, peaks):
    """The one thing kept from ``bench.py``: a timing that did not wait for
    the device reads as an impossible rate, so refuse it instead of
    recording it."""
    share = mfu(samples_per_s, flops_per_sample, chips, peaks)
    if share > 1.0:
        raise ImplausibleRate(
            f"{samples_per_s:.6g} samples/s x {flops_per_sample:.6g} FLOP "
            f"implies {share:.2f}x the published peak of {chips} chip(s) "
            f"({peaks['flops_per_s']:.3g} FLOP/s each): the timing did not "
            f"cover the device's work"
        )
    return share

"""Per-layer quantities of a traced window, shared by the metric readers.

A reader under ``metrics/`` gets a :class:`Window` and returns a number, or
None when the window holds nothing it can read (no step that admitted
nothing, no admission).  Steps of the harness's record and ``bench.step``
spans of the trace are matched by order; where their counts differ the
step-based quantities are None rather than guessed.

The admission/decode split uses only the harness's spans and device busy
time, so it holds whatever the program names its programs: a step that
admitted nothing is one decode; a step that admitted requests is one decode
plus their admissions (prefill, splice into the slot cache, first-token
selection).
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional, Sequence, Tuple

from chip import counts
from chip.shapes import Shape


@dataclasses.dataclass(frozen=True)
class Step:
    """The harness's record of one ``Server.step``."""
    start: float                # host clock, seconds
    end: float
    admitted: Tuple[int, ...]   # prompt lengths of requests admitted
    ctxs: Tuple[int, ...]       # context each decoded sequence attended over


@dataclasses.dataclass
class Window:
    shape: Shape
    peaks: Dict
    window_s: float             # traced window, trace clock
    busy_s: float               # device busy in it
    steps: List[Step]
    step_busy_s: Optional[List[float]]  # per step, None if unmatched
    step_dur_s: Optional[List[float]]


def _decode_only(w: Window) -> List[int]:
    return [i for i, s in enumerate(w.steps) if not s.admitted and s.ctxs]


def _admitting(w: Window) -> List[int]:
    return [i for i, s in enumerate(w.steps) if s.admitted]


def _mean(xs: Sequence[float]) -> Optional[float]:
    return sum(xs) / len(xs) if xs else None


def device_idle_pct(w: Window) -> Optional[float]:
    if w.window_s <= 0:
        return None
    return 100.0 * (1.0 - w.busy_s / w.window_s)


def server_host_ms_per_step(w: Window) -> Optional[float]:
    """Device-idle time inside step spans, per step."""
    if not w.step_dur_s or w.step_busy_s is None:
        return None
    return 1e3 * _mean([d - b for d, b in zip(w.step_dur_s, w.step_busy_s)])


def decode_s_per_step(w: Window) -> Optional[float]:
    """Device busy time of a step that admitted nothing."""
    if w.step_busy_s is None:
        return None
    return _mean([w.step_busy_s[i] for i in _decode_only(w)])


def decode_ms_per_step(w: Window) -> Optional[float]:
    d = decode_s_per_step(w)
    return None if d is None else 1e3 * d


def admission_s(w: Window) -> Optional[Tuple[float, int, List[int]]]:
    """(device seconds of admission, requests admitted, admitting steps):
    busy time of admitting steps less one decode each."""
    d = decode_s_per_step(w)
    adm = _admitting(w)
    if d is None or not adm:
        return None
    secs = sum(w.step_busy_s[i] for i in adm) - d * len(adm)
    return secs, sum(len(w.steps[i].admitted) for i in adm), adm


def admit_ms_per_request(w: Window) -> Optional[float]:
    a = admission_s(w)
    return None if a is None else 1e3 * a[0] / a[1]


def decode_roofline(w: Window) -> Optional[float]:
    """Bytes the decode-only steps need over the peak bandwidth, against
    their device time."""
    idx = _decode_only(w)
    if w.step_busy_s is None or not idx:
        return None
    need = sum(counts.decode_step_bytes(w.shape, w.steps[i].ctxs) for i in idx)
    busy = sum(w.step_busy_s[i] for i in idx)
    return 100.0 * need / w.peaks["hbm_bytes_per_s"] / busy


def window_flops(w: Window) -> int:
    """FLOPs of every token the window processed: prompts admitted and
    tokens decoded."""
    s = w.shape
    return sum(sum(counts.prefill_flops(s, p) for p in st.admitted)
               + sum(counts.decode_token_flops(s, c) for c in st.ctxs)
               for st in w.steps)


def step_mfu_pct(w: Window) -> Optional[float]:
    if w.window_s <= 0 or not w.steps:
        return None
    return 100.0 * window_flops(w) / (w.window_s * w.peaks["bf16_flops_per_s"])


def admit_mfu_pct(w: Window) -> Optional[float]:
    a = admission_s(w)
    if a is None or a[0] <= 0:
        return None
    secs, _, adm = a
    flops = sum(counts.prefill_flops(w.shape, p)
                for i in adm for p in w.steps[i].admitted)
    return 100.0 * flops / (secs * w.peaks["bf16_flops_per_s"])

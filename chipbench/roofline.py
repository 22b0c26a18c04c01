"""Least bytes of the batched static lane kernel, and the peak it is held to.

The kernel steps integer state machines with no floating-point work worth
a bound, so it is bound by memory traffic.  The least traffic is what has
to cross HBM whatever implements the step:

* per scheduled lane-step, the transaction record it reads and the output
  record it writes;
* per lane and dispatch, the lane's carried state, read once and written
  once.

Per-step table traffic (candidate masks, distances, port tables) is left
out: it belongs to an implementation, and one that keeps it on chip must
not read above 100%.  Scheduled lane-steps are the planner's count
(``steps`` of each group record in ``bench.PERF``), padding included.
"""
from __future__ import annotations

import json
import os

from chipbench.reference.interconnect import layout

WORD = 4  # every field is one int32 (flags as 0/1 words)
# TxnArrays: arrival, kind, plane, node, row, nbytes, op_ticks, valid
TXN_WORDS = 8
# StepOut: completion, wait, conflict, hops, tries, scout_steps, misroutes,
# bus_hold, link_hold, failed
OUT_WORDS = 10
# the group variant the kernel runs in
STATIC_VARIANT = "batched"


def state_words(ssd: dict) -> int:
    """Carried state of one lane: each plane keeps its free-at, and every
    resource of the one unified resource vector keeps free-at plus one
    remembered gap (three words)."""
    lay = layout(ssd["rows"], ssd["cols"])
    planes = (ssd["rows"] * ssd["cols"] * ssd["dies_per_chip"]
              * ssd["planes_per_die"])
    return planes + 3 * lay.R_pad


def least_bytes(groups: list, config: dict) -> dict:
    """``{"static_step": least HBM bytes}`` over the dispatched ``groups``;
    empty where none ran the static kernel."""
    mine = [g for g in groups if g["variant"] == STATIC_VARIANT]
    if not mine:
        return {}
    steps = sum(g["steps"] for g in mine)
    lanes = sum(g["lanes"] for g in mine)
    return {"static_step": WORD * (steps * (TXN_WORDS + OUT_WORDS)
                                   + 2 * lanes * state_words(config["ssd"]))}


def peak(device_kind: str, path: str | None = None) -> dict:
    path = path or os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "peaks.json")
    with open(path) as f:
        table = json.load(f)["devices"]
    if device_kind not in table:
        raise KeyError(f"no peak for device kind {device_kind!r} in "
                       f"{path}; add it with its source")
    return table[device_kind]


def share(least: float, kernel_s: float, hbm_bytes_per_s: float) -> float:
    """Percent of the HBM roofline: least bytes over what the peak moves
    in the kernel's device time."""
    return 100.0 * least / (kernel_s * hbm_bytes_per_s)

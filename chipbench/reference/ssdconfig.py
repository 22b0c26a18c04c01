"""The SSD deployment as the reference reads it: a configuration file's
``ssd`` block plus its time resolution.

Simulated time is integer ticks of ``tick_ns`` nanoseconds.  The
configurations state 10 ns; the control of the correctness check runs the
same reference at a coarser tick (see ``chipbench/check.py``).
"""
from __future__ import annotations

import dataclasses
import math


@dataclasses.dataclass(frozen=True)
class SSD:
    name: str
    rows: int
    cols: int
    dies_per_chip: int
    planes_per_die: int
    pages_per_block: int
    page_bytes: int
    t_read_us: float
    t_prog_us: float
    t_erase_us: float
    cmd_ns: float
    chan_gbps: float
    link_ghz: float
    scout_flit_ns: float
    bus_protocol_ovh_ns: float
    chunk_pages: int
    tick_ns: int = 10

    @classmethod
    def from_file(cls, conf: dict, tick_ns: int | None = None) -> "SSD":
        return cls(name=conf["name"], **conf["ssd"],
                   tick_ns=tick_ns or conf["tick_ns"])

    def ns_to_ticks(self, ns: float) -> int:
        return int(math.ceil(ns / self.tick_ns))

    def us_to_ticks(self, us: float) -> int:
        return self.ns_to_ticks(us * 1e3)

    @property
    def n_chips(self) -> int:
        return self.rows * self.cols

    @property
    def n_planes(self) -> int:
        return self.n_chips * self.dies_per_chip * self.planes_per_die

    @property
    def t_read(self) -> int:
        return self.us_to_ticks(self.t_read_us)

    @property
    def t_prog(self) -> int:
        return self.us_to_ticks(self.t_prog_us)

    @property
    def t_erase(self) -> int:
        return self.us_to_ticks(self.t_erase_us)

    @property
    def t_cmd(self) -> int:
        return max(1, self.ns_to_ticks(self.cmd_ns))

    @property
    def t_bus_ovh(self) -> int:
        return self.ns_to_ticks(self.bus_protocol_ovh_ns)

    @property
    def scout_hop_ns(self) -> int:
        return int(round(self.scout_flit_ns))

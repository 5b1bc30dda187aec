"""Timing, geometry and energy of the modelled memory systems.

A copy of the parameters the frozen references read (the paper's Table I
and the geometry of its Sec. III-A), independent of the program's
``repro.core.timing``.  ``RefConfig`` takes the same field names as the
program's ``HMSConfig``, so one configuration file builds both.
"""

from __future__ import annotations

import dataclasses

COLUMN_BYTES = 32
ROW_BYTES = 2048
COLUMNS_PER_ROW = ROW_BYTES // COLUMN_BYTES            # 64
UM_PAGE_BYTES = 4096


@dataclasses.dataclass(frozen=True)
class DeviceTiming:
    cl: int = 14
    rcd: int = 14
    ras: int = 33
    wr: int = 16
    rp: int = 14


DRAM = DeviceTiming(cl=14, rcd=14, ras=33, wr=16, rp=14)
SCM_MODES = {
    "slc": DeviceTiming(cl=14, rcd=60, ras=60, wr=150, rp=14),
    "mlc": DeviceTiming(cl=14, rcd=120, ras=120, wr=1000, rp=14),
    "tlc": DeviceTiming(cl=14, rcd=250, ras=250, wr=2350, rp=14),
}
# SCM capacity of one cell mode relative to MLC (bits per cell 1 : 2 : 3)
SCM_MODE_CAPACITY_VS_MLC = {"slc": 0.5, "mlc": 1.0, "tlc": 1.5}


@dataclasses.dataclass(frozen=True)
class EnergyParams:
    """pJ/bit access energies (Table I)."""

    dram_act: float = 1.17
    dram_pre: float = 0.39
    dram_rd: float = 0.93
    dram_wr: float = 1.02
    scm_act: float = 2.47
    scm_pre_wr: float = 16.82
    scm_rd: float = 0.93
    scm_wr: float = 1.02
    link_pj_per_bit: float = 8.0


@dataclasses.dataclass(frozen=True)
class RefConfig:
    footprint: int = 64 * 1024 * 1024
    r_hbm: float = 0.75
    dram_ratio: float = 0.5
    line_bytes: int = 256
    organization: str = "hms"
    policy: str = "hms"
    tag_layout: str = "amil"
    scm_mode: str = "mlc"
    channels: int = 8
    banks_per_channel: int = 16
    n_levels: int = 4
    ema_weight: float = 0.01
    use_activation_counter: bool = False
    bear_fill_prob: float = 0.1
    redcache_threshold: int = 2
    ctc_fraction: float = 0.25
    ctc_ways: int = 16
    ctc_sectors_per_line: int = 8
    link_bw_gbps: float = 12.8
    fault_latency_ns: float = 20_000.0
    fault_overlap: float = 16.0
    um_prefetch_pages: int = 4
    um_hot_threshold: int = 4
    act_page_bytes: int = 64 * 1024
    throttle_act: bool = False
    throttle_wr: bool = False
    compute_cycles_per_request: float = 0.05
    energy: EnergyParams = dataclasses.field(default_factory=EnergyParams)

    @property
    def dram_timing(self) -> DeviceTiming:
        return DRAM

    @property
    def _scm_capacity_mlc(self) -> int:
        return int(self.hbm_capacity * (1.0 - self.dram_ratio) * 4.0)

    @property
    def effective_scm_mode(self) -> str:
        if self.scm_mode != "auto":
            return self.scm_mode
        for mode in ("slc", "mlc"):
            cap = int(self._scm_capacity_mlc * SCM_MODE_CAPACITY_VS_MLC[mode])
            if self.footprint <= cap:
                return mode
        return "tlc"

    @property
    def scm_timing(self) -> DeviceTiming:
        base = SCM_MODES[self.effective_scm_mode]
        rcd = base.rcd * (2 if self.throttle_act else 1)
        wr = base.wr * (2 if self.throttle_wr else 1)
        return dataclasses.replace(base, rcd=rcd, wr=wr)

    @property
    def hbm_capacity(self) -> int:
        return int(self.footprint * self.r_hbm)

    @property
    def dram_cache_capacity(self) -> int:
        return int(self.hbm_capacity * self.dram_ratio)

    @property
    def scm_capacity(self) -> int:
        return int(self._scm_capacity_mlc
                   * SCM_MODE_CAPACITY_VS_MLC[self.effective_scm_mode])

    @property
    def num_lines(self) -> int:
        return max(1, self.dram_cache_capacity // self.line_bytes)

    @property
    def lines_per_row(self) -> int:
        return ROW_BYTES // self.line_bytes

    @property
    def columns_per_line(self) -> int:
        return self.line_bytes // COLUMN_BYTES

    @property
    def num_rows(self) -> int:
        return max(1, self.dram_cache_capacity // ROW_BYTES)

    @property
    def ctc_sets(self) -> int:
        """CTC set count, rounded down to a power of two."""
        total = max(self.ctc_ways, int(self.num_rows * self.ctc_fraction))
        raw = max(1, total // (self.ctc_ways * self.ctc_sectors_per_line))
        return 1 << (raw.bit_length() - 1)

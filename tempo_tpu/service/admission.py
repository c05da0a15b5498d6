"""Runtime admission control: project a query's device footprint and
reject/queue it before it compiles or runs.

The static analyzer's ``vmem-budget`` rule folds every kernel call
site's worst-case per-step VMEM bytes at lint time
(``tools/analysis/rules/vmem.py``); its sanctioned *runtime* twin is
the kernel planners' own block folding
(``ops/pallas_kernels._plan`` — the function behind
``pallas_stream.pack_budget``).  This module applies that same folding
per submitted plan:

* **VMEM** — the worst-case per-step block bytes any kernel of the
  plan would hold live (the scoped-VMEM working set).  A query whose
  projection exceeds ``TEMPO_TPU_SERVICE_VMEM_BUDGET`` could NEVER
  run on the declared budget and is **rejected** with
  :class:`AdmissionError` — named, immediate, not queued forever.
* **HBM** — the packed source planes plus the widest intermediate the
  chain materialises (input + output live together).  A query over
  the whole ``TEMPO_TPU_SERVICE_HBM_BUDGET`` is rejected; one that
  merely exceeds the *currently free* share is **queued** until
  running queries release theirs (the scheduler re-checks on every
  release).

The numbers are projections, not accounting: they bound the working
set from the packed geometry the plan declares, which is exactly what
an admission decision needs to be made *before* anything compiles.
"""

from __future__ import annotations

import dataclasses
from typing import Optional

from tempo_tpu.plan import ir

#: total-HBM admission budget (bytes) when the knob is unset and the
#: backend reports no device memory limit (the CPU).
_DEFAULT_HBM_BUDGET = 2 << 30


class AdmissionError(RuntimeError):
    """A query's projected footprint exceeds the service budget — the
    named rejection the admission controller raises instead of queueing
    a query that could never run."""

    def __init__(self, message: str, hbm_bytes: int = 0,
                 vmem_bytes: int = 0):
        super().__init__(message)
        self.hbm_bytes = hbm_bytes
        self.vmem_bytes = vmem_bytes


@dataclasses.dataclass(frozen=True)
class Footprint:
    """Projected device working set of one query."""

    hbm_bytes: int
    vmem_bytes: int


def vmem_budget_bytes() -> int:
    """``TEMPO_TPU_SERVICE_VMEM_BUDGET``; unset = the kernel planners'
    scoped budget (``pallas_kernels._VMEM_BUDGET`` — headroom under
    the 16 MiB scoped-vmem cap), so by default admission rejects
    exactly the shapes the kernels themselves could not block-plan.
    An explicit 0 means 0 (admit nothing) — only *unset* defaults."""
    from tempo_tpu import config
    from tempo_tpu.ops import pallas_kernels as pk

    val = config.get_int("TEMPO_TPU_SERVICE_VMEM_BUDGET")
    return pk._VMEM_BUDGET if val is None else val


def hbm_budget_bytes() -> int:
    """``TEMPO_TPU_SERVICE_HBM_BUDGET``; unset = the memory limit the
    first device reports (16 GB on a v5e chip), or 2 GiB where the
    backend reports none.  An explicit 0 means 0 (admit nothing) —
    only *unset* defaults."""
    import jax

    from tempo_tpu import config

    val = config.get_int("TEMPO_TPU_SERVICE_HBM_BUDGET")
    if val is not None:
        return val
    stats = jax.devices()[0].memory_stats() or {}
    return int(stats.get("bytes_limit", _DEFAULT_HBM_BUDGET))


def _geometry(node: ir.Node) -> Optional[tuple]:
    """(K, L) packed geometry of the frame feeding ``node``, walked
    down the primary input chain to a source; None when no source
    geometry is derivable."""
    import numpy as np

    from tempo_tpu import packing

    cur = node
    while True:
        if cur.op == "dist_source":
            p = cur.payload
            return int(p.K_dev), int(p.L)
        if cur.op == "source":
            lay = cur.payload.layout
            L = packing.pad_length(int(np.max(lay.lengths, initial=0)))
            return int(lay.n_series), L
        if not cur.inputs:
            return None
        cur = cur.inputs[0]


def _node_hbm_bytes(node: ir.Node) -> int:
    """Packed plane bytes this node's result holds live (ts i64 + one
    f32 value + bool validity plane per column), from the optimizer's
    plane-count model; conservative fallback doubles the input."""
    from tempo_tpu.plan import optimizer

    geom = _geometry(node)
    if geom is None:
        return 0
    K, L = geom
    planes = optimizer._device_plane_count(node)
    if planes is None:
        planes = 2 * max(1, len(node.inputs))
    return K * L * (8 + 5 * int(planes))


#: conservative live-plane counts of the kernel block plans, mirroring
#: the static rule's per-site folding: the window engines hold carries
#: + roll temps + pipelined I/O (~16 [bk, L] f32 planes), the merge
#: network ~12 over the merged lane axis.
_OP_VMEM_ARRAYS = {
    "range_stats": 16,
    "fused_asof_stats_ema": 16,
    "asof_join": 12,
}


def _node_vmem_bytes(node: ir.Node) -> int:
    """Worst-case per-step VMEM block bytes of the kernel this op would
    run, via the kernel planners' own folding
    (``pallas_kernels._plan`` — the runtime twin of the analyzer's
    vmem-budget rule).  When even the smallest legal block is over the
    planners' scoped budget, the minimal [8, L] block's bytes are
    reported — the true requirement the admission budget is compared
    against."""
    from tempo_tpu.ops import pallas_kernels as pk

    arrays = _OP_VMEM_ARRAYS.get(node.op)
    if arrays is None:
        return 0
    geom = _geometry(node)
    if geom is None:
        return 0
    K, L = geom
    if node.op == "asof_join":
        right = _geometry(node.inputs[1]) if len(node.inputs) > 1 else None
        L = L + (right[1] if right else L)      # merged lane width
    plan = pk._plan(int(K), int(L), arrays=arrays)
    if plan is None:
        return 8 * L * 4 * arrays               # minimal legal block
    _, bk, _ = plan
    return bk * L * 4 * arrays


def project_footprint(root: ir.Node) -> Footprint:
    """Project one plan's working set: all source planes resident plus
    the two widest op results (an op's input and output are live
    together), and the largest kernel block any op folds."""
    hbm = 0
    op_bytes = []
    vmem = 0
    for n in root.walk():
        if n.is_source():
            hbm += _node_hbm_bytes(n)
        else:
            op_bytes.append(_node_hbm_bytes(n))
            vmem = max(vmem, _node_vmem_bytes(n))
    op_bytes.sort(reverse=True)
    hbm += sum(op_bytes[:2])
    return Footprint(hbm_bytes=int(hbm), vmem_bytes=int(vmem))


class AdmissionController:
    """Budget bookkeeping for the query service.  NOT itself locked —
    the service serializes calls under its scheduler condition, so
    check/acquire/release are plain arithmetic here."""

    def __init__(self, hbm_budget: Optional[int] = None,
                 vmem_budget: Optional[int] = None):
        # None = defaults; an explicit 0 is honoured (admit nothing)
        self.hbm_budget = int(
            hbm_budget_bytes() if hbm_budget is None else hbm_budget)
        self.vmem_budget = int(
            vmem_budget_bytes() if vmem_budget is None else vmem_budget)
        self.hbm_in_use = 0

    def check(self, fp: Footprint) -> None:
        """Raise :class:`AdmissionError` when the query could NEVER run
        under the declared budgets (reject-at-submit, not
        queued-forever)."""
        if fp.vmem_bytes > self.vmem_budget:
            raise AdmissionError(
                f"query rejected: projected worst-case VMEM block "
                f"{fp.vmem_bytes} B exceeds the admission budget "
                f"{self.vmem_budget} B (TEMPO_TPU_SERVICE_VMEM_BUDGET) "
                f"— no block plan fits; the shape cannot run",
                hbm_bytes=fp.hbm_bytes, vmem_bytes=fp.vmem_bytes)
        if fp.hbm_bytes > self.hbm_budget:
            raise AdmissionError(
                f"query rejected: projected HBM footprint "
                f"{fp.hbm_bytes} B exceeds the TOTAL admission budget "
                f"{self.hbm_budget} B (TEMPO_TPU_SERVICE_HBM_BUDGET) — "
                f"it could never be scheduled",
                hbm_bytes=fp.hbm_bytes, vmem_bytes=fp.vmem_bytes)

    def fits_now(self, fp: Footprint) -> bool:
        return self.hbm_in_use + fp.hbm_bytes <= self.hbm_budget

    def acquire(self, fp: Footprint) -> None:
        self.hbm_in_use += fp.hbm_bytes

    def release(self, fp: Footprint) -> None:
        self.hbm_in_use = max(0, self.hbm_in_use - fp.hbm_bytes)

"""Memory-budget admission: price a workload BEFORE dispatching it.

Until now nothing in the system modeled capacity: the first matrix whose
bucket slabs out-size a chip's HBM was a raw ``RESOURCE_EXHAUSTED`` crash
that ``utils.retry`` futilely re-OOMed and ``run_pipeline`` journaled as a
generic stage failure. ALX (arxiv 2112.02194) makes sharded-beyond-one-chip
factors the next scale step and iALS++ (arxiv 2110.14044) pushes ranks to
128/256 — both multiply memory pressure, so exhaustion must become a
*handled* failure mode before those land.

The planner is a **static cost model from shapes and dtypes**: every
dispatch seam knows its slab shapes before any byte moves (bucket plans,
factor-table dims, ladder rungs), so pricing is host arithmetic — no probe
allocation, no device round-trip. Costs are deliberately coarse (they ignore
allocator fragmentation and XLA scratch), which is why admission compares
against a *headroom-scaled* budget and why, where an AOT handle exists, the
static estimate is cross-checked against the compiler's own
``compiled.memory_analysis()`` (:func:`compiled_memory_bytes`).

One admission call returns a verdict:

``fit``      the priced bytes fit the budget: dispatch the resident path.
``degrade``  over budget but the caller declared a degraded mode (chunked
             host-streamed ALS groups, a lower fold-in ladder rung): take it.
``refuse``   over budget with no degraded mode (a hot-swap candidate that
             cannot sit alongside the incumbent): a recorded rejection,
             never a crash.

Verdicts are counted in ``albedo_capacity_verdicts_total{verdict=,workload=}``.
The ``capacity.admit`` fault site fires inside every admission; arming the
new ``oom`` kind forces the over-budget path (the injected
``RESOURCE_EXHAUSTED`` is caught HERE and converted to degrade/refuse), so
chaos drills exercise the real degraded machinery without a 16 GB
allocation.

Budget detection (per device):

1. ``ALBEDO_DEVICE_MEM_BYTES`` — explicit override, the CPU-CI knob and the
   chaos-drill pressure valve (suffixes k/m/g accepted).
2. On an accelerator: ``jax.local_devices()[0].memory_stats()
   ["bytes_limit"]`` — what the runtime actually reports. A device that
   reports none is an ERROR, never priced at the host's RAM or a guess: an
   admission against the wrong budget is how an over-HBM workload gets
   dispatched.
3. On the CPU backend: ``/proc/meminfo`` MemTotal (host RAM is device RAM).

``ALBEDO_MEM_HEADROOM`` (default 0.85) scales the detected total into the
admission budget; ``ALBEDO_CAPACITY=off`` disables admission entirely
(everything verdicts ``fit`` — the escape hatch if the cost model ever
refuses a workload that would in fact fit).
"""

from __future__ import annotations

import dataclasses
import logging
import os

import numpy as np

from albedo_tpu.utils import events, faults
from albedo_tpu.utils.retry import is_resource_exhausted

log = logging.getLogger(__name__)

ADMIT_FAULT = faults.site("capacity.admit")

_ENV_BYTES = "ALBEDO_DEVICE_MEM_BYTES"
_ENV_HEADROOM = "ALBEDO_MEM_HEADROOM"
_ENV_TOGGLE = "ALBEDO_CAPACITY"
_DEFAULT_HEADROOM = 0.85


class CapacityExceeded(MemoryError):
    """An admission verdict of ``refuse`` where the caller cannot proceed at
    all — carries the verdict so journals/reports can record the pricing.

    Subclasses :class:`MemoryError` ON PURPOSE: ``utils.retry.
    is_resource_exhausted`` classifies MemoryError as permanent, so a
    deterministic capacity refusal fails FAST through the pipeline's stage
    retries instead of re-pricing the identical refusal through the whole
    backoff budget — the same fail-fast contract a real device OOM gets."""

    def __init__(self, verdict: "AdmissionVerdict"):
        super().__init__(
            f"workload {verdict.workload!r} needs ~{verdict.required_bytes:,} "
            f"bytes against a {verdict.budget_bytes:,}-byte budget "
            f"(refused: capacity)"
        )
        self.verdict = verdict


def _parse_bytes(raw: str) -> int:
    raw = raw.strip().lower()
    mult = 1
    if raw and raw[-1] in "kmg":
        mult = {"k": 1 << 10, "m": 1 << 20, "g": 1 << 30}[raw[-1]]
        raw = raw[:-1]
    return int(float(raw) * mult)


def enabled() -> bool:
    return os.environ.get(_ENV_TOGGLE, "on").lower() not in ("off", "0", "false")


def device_memory_bytes() -> int:
    """Detected per-device memory (bytes). See module doc for the order."""
    raw = os.environ.get(_ENV_BYTES)
    if raw:
        return _parse_bytes(raw)
    import jax

    dev = jax.local_devices()[0]
    if dev.platform != "cpu":
        stats = dev.memory_stats()
        if not stats or not stats.get("bytes_limit"):
            raise RuntimeError(
                f"{dev.platform} device {dev.device_kind!r} reports no "
                f"memory_stats()['bytes_limit'] (got {stats!r}); set "
                f"{_ENV_BYTES} to price admissions on this backend"
            )
        return int(stats["bytes_limit"])
    with open("/proc/meminfo") as f:
        for line in f:
            if line.startswith("MemTotal:"):
                return int(line.split()[1]) * 1024
    raise RuntimeError(f"/proc/meminfo has no MemTotal; set {_ENV_BYTES}")


def headroom() -> float:
    try:
        h = float(os.environ.get(_ENV_HEADROOM, _DEFAULT_HEADROOM))
    except ValueError:
        h = _DEFAULT_HEADROOM
    return min(1.0, max(0.05, h))


def budget_bytes() -> int:
    """The admission budget: detected per-device memory x headroom."""
    return int(device_memory_bytes() * headroom())


@dataclasses.dataclass(frozen=True)
class CapacityPlan:
    """A priced workload: named byte items summing to ``required_bytes``.

    ``items`` keeps the per-component split (factor tables, slabs, transient
    gather blocks) so a ``refused: capacity`` journal entry tells the
    operator WHAT is too big, not just that something is.
    """

    workload: str
    items: dict[str, int]

    @property
    def required_bytes(self) -> int:
        return int(sum(self.items.values()))

    def to_dict(self) -> dict:
        return {
            "workload": self.workload,
            "required_bytes": self.required_bytes,
            "items": {k: int(v) for k, v in self.items.items()},
        }


@dataclasses.dataclass(frozen=True)
class AdmissionVerdict:
    """The outcome of one admission: verdict + the numbers behind it.

    ``chosen`` names the workload of the plan the admission actually
    selected — for plain :func:`admit` it equals ``workload``; for
    :func:`admit_ladder` it is the first rung that fit the budget.
    """

    workload: str
    verdict: str  # "fit" | "degrade" | "refuse"
    required_bytes: int
    budget_bytes: int
    detail: str = ""
    plan: CapacityPlan | None = None
    chosen: str = ""

    @property
    def fits(self) -> bool:
        return self.verdict == "fit"

    def to_dict(self) -> dict:
        out = {
            "workload": self.workload,
            "verdict": self.verdict,
            "required_bytes": int(self.required_bytes),
            "budget_bytes": int(self.budget_bytes),
            "detail": self.detail,
        }
        if self.chosen:
            out["chosen"] = self.chosen
        if self.plan is not None:
            out["items"] = {k: int(v) for k, v in self.plan.items.items()}
        return out


def admit(
    plan: CapacityPlan,
    *,
    degradable: bool = False,
    budget: int | None = None,
    fallback_plan: CapacityPlan | None = None,
) -> AdmissionVerdict:
    """Price ``plan`` against the budget and return the verdict.

    ``degradable`` declares that the caller HAS a cheaper mode to fall back
    to — over-budget then verdicts ``degrade`` instead of ``refuse``. When
    the fallback itself has a priceable plan, pass it as ``fallback_plan``:
    a fallback that ALSO busts the budget turns the verdict into ``refuse``
    — one admission, one counted verdict, never a degrade that could not
    actually run. The ``capacity.admit`` fault site fires on every
    admission; an injected ``oom`` is caught here and converted to the
    over-budget verdict (chaos drives the real degrade path), while other
    injected kinds propagate like any fault-site error.
    """
    budget = budget_bytes() if budget is None else int(budget)
    required = plan.required_bytes
    forced = ""
    if enabled():
        try:
            ADMIT_FAULT.hit()
        except Exception as e:  # noqa: BLE001 — only OOM converts; rest propagate
            if not is_resource_exhausted(e):
                raise
            forced = f" (forced over-budget by injected fault: {e})"
            required = max(required, budget + 1)
    over = enabled() and required > budget
    # An injected oom must land on the DEGRADE path (that is the drill);
    # only a genuinely over-budget fallback refuses.
    fallback_fits = fallback_plan is None or forced or (
        fallback_plan.required_bytes <= budget
    )
    if not over:
        verdict = "fit"
        detail = f"{required:,} bytes within {budget:,}-byte budget"
    elif degradable and fallback_fits:
        verdict = "degrade"
        detail = (
            f"{required:,} bytes over the {budget:,}-byte budget; "
            f"taking the degraded path{forced}"
        )
    elif degradable:
        verdict = "refuse"
        detail = (
            f"{required:,} bytes over the {budget:,}-byte budget and the "
            f"degraded plan needs {fallback_plan.required_bytes:,} bytes "
            f"itself{forced}"
        )
    else:
        verdict = "refuse"
        detail = (
            f"{required:,} bytes over the {budget:,}-byte budget and no "
            f"degraded mode{forced}"
        )
    out = AdmissionVerdict(
        workload=plan.workload, verdict=verdict, required_bytes=required,
        budget_bytes=budget, detail=detail, plan=plan,
    )
    events.capacity_verdicts.inc(verdict=verdict, workload=plan.workload)
    if verdict != "fit":
        log.warning("capacity admission [%s]: %s", plan.workload, detail)
    return out


def admit_ladder(
    plans: list[CapacityPlan],
    *,
    budget: int | None = None,
) -> AdmissionVerdict:
    """Admission over an ordered degradation ladder of priced plans.

    ``plans[0]`` is the preferred mode; each later plan is a cheaper
    degraded mode. The verdict is ``fit`` when the first plan fits,
    ``degrade`` when a later rung is the first that fits (``chosen`` names
    it), and ``refuse`` when no rung fits. Like :func:`admit`, one call =
    one counted verdict, and the ``capacity.admit`` fault site fires once —
    an injected ``oom`` forces the preferred rung over budget so the drill
    lands on the first degraded rung (never a crash).
    """
    if not plans:
        raise ValueError("admit_ladder needs at least one plan")
    budget = budget_bytes() if budget is None else int(budget)
    required = [p.required_bytes for p in plans]
    forced = ""
    if enabled():
        try:
            ADMIT_FAULT.hit()
        except Exception as e:  # noqa: BLE001 — only OOM converts; rest propagate
            if not is_resource_exhausted(e):
                raise
            forced = f" (forced over-budget by injected fault: {e})"
            required[0] = max(required[0], budget + 1)
    if not enabled():
        chosen = 0
    else:
        chosen = next(
            (i for i, r in enumerate(required) if r <= budget or (forced and i == 1)),
            len(plans),
        )
    if chosen == 0:
        verdict = "fit"
        detail = (
            f"{required[0]:,} bytes within {budget:,}-byte budget"
        )
    elif chosen < len(plans):
        verdict = "degrade"
        detail = (
            f"{required[0]:,} bytes over the {budget:,}-byte budget; taking "
            f"degraded rung {chosen} ({plans[chosen].workload}: "
            f"{required[chosen]:,} bytes){forced}"
        )
    else:
        verdict = "refuse"
        detail = (
            f"every rung over the {budget:,}-byte budget "
            f"({', '.join(f'{p.workload}={r:,}' for p, r in zip(plans, required))})"
            f"{forced}"
        )
    idx = min(chosen, len(plans) - 1)
    out = AdmissionVerdict(
        workload=plans[0].workload, verdict=verdict,
        required_bytes=required[0], budget_bytes=budget, detail=detail,
        plan=plans[idx], chosen=plans[idx].workload if verdict != "refuse" else "",
    )
    events.capacity_verdicts.inc(verdict=verdict, workload=plans[0].workload)
    if verdict != "fit":
        log.warning("capacity admission [%s]: %s", plans[0].workload, detail)
    return out


# --- static cost models -------------------------------------------------------
# All coarse, all conservative-ish, all pure host arithmetic. f32 = 4 bytes;
# the gather dtype may halve the streamed block. Each model prices what is
# RESIDENT for the workload's lifetime plus the single largest transient the
# program materializes at once.


def _dtype_bytes(gather_dtype: str | None) -> int:
    return 2 if gather_dtype == "bfloat16" else 4


def bucket_slab_bytes(b: int, ln: int) -> int:
    """Bytes of one padded ``(b, ln)`` bucket's slab: int32 row ids, int32
    indices, float32 values, one-byte mask."""
    return b * 4 + b * ln * (4 + 4 + 1)


def exact_system_bytes(rank: int) -> int:
    """Bytes one system of a bucket holds on the device while
    ``ops.als.bucket_solve_body`` solves it exactly: the three arrays
    ``als.cholesky.build`` makes of it, priced as if none took another's
    place - the correction as the contraction leaves it (from a line table
    ``(LANES, LANES)``, folded to ``(rank, rank)`` afterwards; else ``(rank,
    rank)`` at the size the (8, 128) tiling pads it to), the augmented system
    ``(rank + 1, rank)`` at that padding, and the same again for its transpose
    into the lanes (``(rank, rank + 1, .)``: no more a lane than that). The
    solve's own working set (a chunk of systems and its factor,
    ``ops.als.EXACT_CHUNK_BYTES``, in VMEM on a v5e) is inside that room.
    Read off the memory analysis of one bucket's program compiled for a
    described v5e (PERF.md section 6, PR 36): at 8,192 x 8 the compiler holds
    the gathered block, the correction and ONE augmented system at once -
    139.3 KB a slot row at rank 128 (block 4.1 + 65.5 + 69.6), 94.5 at rank 50
    (4.1 + 65.5 + 28.7, less what it overlays), 65.6 at rank 16 - so the third
    array is the room: a bucket's compiled temporaries are 0.53-0.73 of its
    price (:func:`exact_bucket_bytes`) at rank 128, 0.63-0.81 at 50 and
    0.67-0.86 at 16 over 8,192 x 8 / 16 / 32, 4,096 x 64, 2,048 x 176 and
    512 x 1,064, and 0.98-0.99 for 16 rows of 125,104 entries, which are all
    block. The library's batched factorisation held 131.6 / 123.2 / 73.8 KB a
    system (PR 35)."""
    from albedo_tpu.ops.als import LANES, gather_packs_rows

    wide = -(-rank // LANES) * LANES * 4

    def rows(n: int) -> int:
        return -(-n // 8) * 8

    correction = LANES * LANES * 4 if gather_packs_rows(rank) else rows(rank) * wide
    return correction + 2 * rows(rank + 1) * wide


def exact_bucket_bytes(b: int, ln: int, rank: int, gather_bytes: int = 4) -> int:
    """Bytes a ``(b, ln)`` bucket's exact solve holds at once: its gathered
    block at the width it is gathered at (a whole ``LANES``-lane line an entry
    from a line table, whatever the rank) with the entries' weights, and its
    systems (:func:`exact_system_bytes`) - one a slot row of the gathered
    block, and no fewer than the whole lane tiles the solve pads a small
    block's to."""
    from albedo_tpu.ops.als import LANES, exact_lanes, gather_packs_rows, gather_slots

    width = LANES if gather_packs_rows(rank) else rank
    systems = max(gather_slots(b, ln), exact_lanes(b))
    return b * ln * (width * gather_bytes + gather_bytes) + systems * exact_system_bytes(rank)


def plan_fit(
    bucket_shapes_user: list[tuple[int, int]],
    bucket_shapes_item: list[tuple[int, int]],
    n_users: int,
    n_items: int,
    rank: int,
    gather_dtype: str | None = None,
    n_devices: int = 1,
    solver: str = "cg",
) -> CapacityPlan:
    """Price the device-resident fused ALS fit, PER DEVICE.

    Resident: both factor tables, every uploaded bucket slab (row_ids + idx
    + val + mask for BOTH sides — the whole point of the resident path is
    that ratings stay on device across sweeps), and the landing pools
    (``concat(solved_blocks..., target)`` materializes ``n_slots + n_target``
    rank-vectors per half-sweep). Transient: the largest bucket's gathered
    ``(B, L, rank)`` block plus, under ``solver="cg"``, its ``(B, rank,
    rank)`` Gramian correction, and under the exact solve what its
    ``gather_slots(B, L)`` systems hold together (:func:`exact_system_bytes`:
    12.3 times the one correction at rank 50) beside the block at the width
    it is gathered at (:func:`exact_bucket_bytes`). ``solver`` defaults to the
    price this plan had before it took one.

    ``n_devices > 1`` prices the GSPMD mesh-resident path: factor tables
    (and the landing pool's target segment) stay REPLICATED per device,
    while slabs, solved-slot pools, and transients split over the batch
    axis — the replicated tables are exactly why this path stops scaling
    and the fully sharded plan (:func:`plan_fit_sharded`) takes over.
    """
    from albedo_tpu.ops.als import check_solver

    check_solver(solver)
    gb = _dtype_bytes(gather_dtype)
    n = max(1, int(n_devices))
    tables = (n_users + n_items) * rank * 4

    def in_flight(b: int, ln: int) -> int:
        if solver == "cg":
            return b * ln * (rank * gb + gb) + b * rank * rank * 4
        return exact_bucket_bytes(b, ln, rank, gb)

    slabs = 0
    slots_u = slots_i = 0
    transient = 0
    for shapes, side in ((bucket_shapes_user, "u"), (bucket_shapes_item, "i")):
        for b, ln in shapes:
            slabs += bucket_slab_bytes(b, ln)
            if side == "u":
                slots_u += b
            else:
                slots_i += b
            transient = max(transient, in_flight(b, ln))
    landing = ((slots_u + slots_i) // n + n_users + n_items) * rank * 4
    return CapacityPlan(
        workload="als_fit",
        items={
            "factor_tables": tables,
            "bucket_slabs": slabs // n,
            "landing_pools": landing,
            "transient_gather": transient // n,
        },
    )


def _shard_pad(n: int, n_devices: int) -> int:
    return -(-n // n_devices) * n_devices


def plan_fit_sharded(
    bucket_shapes_user: list[tuple[int, int]],
    bucket_shapes_item: list[tuple[int, int]],
    n_users: int,
    n_items: int,
    rank: int,
    n_devices: int,
    gather_dtype: str | None = None,
    streamed: bool = False,
    mode: str = "allgather",
    solver: str = "cholesky",
    pipelined: bool = True,
) -> CapacityPlan:
    """Price the fully sharded ALS fit (ALX layout), PER DEVICE.

    Resident: 1/n of BOTH row-sharded factor tables, plus (non-streamed)
    1/n of every bucket slab.

    **Resident buckets under** ``mode="allgather"`` (the dataflow of
    ``parallel/als.py``, "the resident dataflow": every device solves its
    OWN rows) price one half-sweep's transient as it is held: the source
    table assembled ONCE, whole, in float32 (``assembled_source_table``: the
    larger side's, or the smaller's where the other half-sweep's landing
    outweighs it) and beside it the largest of three things that are not
    live at once: the copy of that table the assembly's program holds while
    it runs (``assembly_copy``: compiled for a v5e the program is an
    all-gather into a temporary and a copy into its result, temporary =
    result = the table; the seeded draw and the relayouts hold a whole
    table as a temporary too), one bucket's gathered block and Gramian
    correction at its full slot count (``bucket_in_flight``: a device's own
    rows fill a bucket as a chip's do), and the landing — the target side's
    solved blocks, its shard, their concatenation and the new shard
    (``landing_pool``). No target table is assembled for the CG warm start
    (rows are read from the device's own shard) and no solved row travels.
    ``bucket_shapes_*`` are the planner's global shapes: a shard's own are
    the same tiers, a few slots apart. At 10M x 1M, rank 128, four chips the
    price is 12.20 GB a chip; the chips' allocators peaked at 9.56 - 10.73 GB
    (PERF.md section 4), over the 7.99 GB that a plan without the copy gave.

    **Streamed buckets, and the ring:** streamed mode keeps only the
    in-flight bucket slab shards on device — the star matrix is never
    device-resident whole: under the default PIPELINED dataflow the
    double-buffered prefetch holds **two** bucket slabs at once (the one
    being solved plus the one the background uploader just landed), priced
    as the worst same-side pair of slab shards — both in-flight buckets
    always belong to one half-sweep; ``pipelined=False`` is the synchronous
    dataflow's single slab — which is why the admission ladder can pick
    unpipelined-streamed as a cheaper rung below pipelined-streamed.
    Transient, per bucket (these programs assemble inside every bucket's
    program): the assembled source factors — the FULL (padded) table under
    ``mode="allgather"``, a double-buffered 1/n shard ring slot under
    ``mode="ring"`` — plus the local gathered block, its Gramian
    correction, and the all-gathered solved rows of the bucket. There the
    CG solver additionally all-gathers the target table for its warm-start
    rows, so its transient prices BOTH tables under all-gather.
    """
    gb = _dtype_bytes(gather_dtype)
    n = max(1, int(n_devices))
    u_pad, i_pad = _shard_pad(n_users, n), _shard_pad(n_items, n)
    tables = (u_pad + i_pad) * rank * 4 // n
    slabs = 0
    worst_slab = 0
    worst_pair = 0
    transient = 0
    for shapes, src_rows, tgt_rows in (
        (bucket_shapes_user, i_pad, u_pad),  # user solves gather item factors
        (bucket_shapes_item, u_pad, i_pad),
    ):
        if mode == "ring":
            # Two ring slots in flight (the held shard + the arriving one).
            assembled = 2 * (src_rows // n) * rank * gb
        else:
            assembled = src_rows * rank * gb
            if solver == "cg":
                assembled += tgt_rows * rank * 4  # warm-start gather
        side_worst = side_second = 0
        for b, ln in shapes:
            slab = b * 4 + b * ln * (4 + 4 + 1)
            slabs += slab // n
            worst_slab = max(worst_slab, slab // n)
            if slab // n >= side_worst:
                side_worst, side_second = slab // n, side_worst
            elif slab // n > side_second:
                side_second = slab // n
            local = (
                (b // n) * ln * (rank * gb + gb)
                + (b // n) * rank * rank * 4
                + b * rank * 4  # all-gathered solved rows land on every device
            )
            transient = max(transient, assembled + local)
        # The double-buffer only ever holds buckets of ONE half-sweep, so
        # the pipelined in-flight peak is the worst SAME-SIDE pair (a
        # one-bucket side never double-buffers itself).
        worst_pair = max(worst_pair, side_worst + side_second)
    items = {
        "factor_table_shards": tables,
        "transient_assembly": transient,
    }
    if not streamed and mode == "allgather":
        items = {"factor_table_shards": tables, **_resident_sharded_transient(
            bucket_shapes_user, bucket_shapes_item, u_pad, i_pad, rank, gb, n)}
    workload = "als_fit_sharded"
    if streamed and pipelined:
        # Double-buffered prefetch: the bucket being solved + the one the
        # background uploader holds — the two largest slabs of one side.
        items["streamed_slabs_in_flight"] = worst_pair
        workload = "als_fit_sharded_streamed"
    elif streamed:
        items["streamed_slab_in_flight"] = worst_slab
        workload = "als_fit_sharded_streamed_sync"
    else:
        items["bucket_slab_shards"] = slabs
    return CapacityPlan(workload=workload, items=items)


def _resident_sharded_transient(shapes_user, shapes_item, u_pad, i_pad, rank, gb, n) -> dict:
    """The larger half-sweep's transient of the resident row-sharded
    dataflow, by item (``plan_fit_sharded`` says what each is)."""
    sides = []
    for shapes, src_rows, tgt_rows in ((shapes_user, i_pad, u_pad), (shapes_item, u_pad, i_pad)):
        # A shard holds 1/n of a length tier's rows, in buckets as full as
        # the planner's: its largest has the tier's slots over n, or a whole
        # bucket's where the tier has more.
        tiers: dict[int, list[int]] = {}
        for b, ln in shapes:
            tiers.setdefault(ln, []).append(b)
        block = max(
            (min(max(bs), -(-sum(bs) // n)) * (ln * (rank * gb + gb) + rank * rank * 4)
             for ln, bs in tiers.items()),
            default=0,
        )
        # the solved blocks, their concatenation with the old shard (which
        # ``factor_table_shards`` holds), and the new shard
        pool = 2 * (sum(b for b, _ in shapes) // n + tgt_rows // n) * rank * 4
        # the assembly's program holds the table twice while it runs
        copy = src_rows * rank * 4
        # (the copy, a bucket's block and the landing are not live at once)
        beside = {"assembly_copy": copy, "bucket_in_flight": block, "landing_pool": pool}
        largest = max(beside, key=beside.get)
        sides.append({"assembled_source_table": src_rows * rank * 4, largest: beside[largest]})
    return max(sides, key=lambda side: sum(side.values()))


# The (B, rank) float32 arrays that ``ops.als.chunked_bucket_update`` holds
# beside a bucket's gathered block while it solves it under ``solver="cg"``:
# the warm start, the right-hand side, the preconditioner, the iterate, the
# residual, the search direction, its product with the system, and the solved
# block ahead of the landing. Counted in the programs' memory analysis,
# compiled on the host for a described v5e (``jax.experimental.topologies``,
# "v5e:2x2": no chip call) at rank 128, as temporaries less the ``(B, L, k)``
# block over ``B * rank * 4``: 5.0 and 6.0 for the merged one-entry dispatches
# of 10M x 1M x 100M stars ((169984, 1) into the 10M-row table, (240640, 1)
# into the 1M-row one), 3.2 at (8192, 64), 6.8 at (8192, 176); at 8,192 rows
# of ``L`` <= 16 the whole program's temporaries are 0.7 MB (VMEM). Slab +
# temporaries of every shape that layout dispatches come to 0.02...0.994 of
# the price below (the most: (8192, 176) and the items' (239, 8768)). A
# longer tier merged to the same cap would hold more - 8.1 at (95232, 8), 8.2
# at (62464, 16), slab + temporaries 1.002 of the price - so raise this to 9
# before ``models.als.STREAM_MERGED_LEN`` grows (PERF.md section 6, PR 34).
# The exact solve's row state is small beside its (B, rank, rank) systems; it
# is priced the same eight.
CHUNKED_ROW_ARRAYS = 8


def chunked_row_bytes(ln: int, rank: int, gather_dtype: str | None, solver: str) -> int:
    """Bytes one slot row of a padded length-``ln`` bucket holds on the device
    while the chunked fit solves it: its share of the slab, its gathered
    ``(ln, rank)`` block, its ``(rank, rank)`` system where the solve builds
    one (the exact solve always; the CG on rows of ``ops.als.cg_uses_gramian``
    length), and ``CHUNKED_ROW_ARRAYS`` rank-vectors. What the chunked fit
    fills a dispatch of one-entry rows by
    (``models.als.ImplicitALS._dispatch_rows``), and what
    :func:`plan_fit_chunked` prices a bucket by."""
    from albedo_tpu.ops.als import cg_uses_gramian

    gb = _dtype_bytes(gather_dtype)
    system = rank * rank * 4 if solver != "cg" or cg_uses_gramian(ln, rank) else 0
    return (
        bucket_slab_bytes(1, ln) + ln * (rank * gb + gb) + system
        + CHUNKED_ROW_ARRAYS * rank * 4
    )


def stream_table_rows(shapes: list[tuple[int, int]], n_rows: int) -> int:
    """Rows of one factor table held in the chunked fit's dispatch order
    (``models.als.StreamLayout``), at most: every logical row once, and a
    row of its own for each padding slot. Only a length tier's last bucket
    has padding slots, fewer than 1,024 and fewer than the tier's rows
    (``ragged.plan_buckets``' slot tiers), whatever rows a dispatch of the
    tier carries, so the planner's shapes at ``batch_size`` rows bound the
    streamed layout's too."""
    tiers: dict[int, int] = {}
    for b, ln in shapes:
        tiers[ln] = tiers.get(ln, 0) + b
    return n_rows + sum(min(1023, slots) for slots in tiers.values())


def plan_fit_chunked(
    bucket_shapes_user: list[tuple[int, int]],
    bucket_shapes_item: list[tuple[int, int]],
    n_users: int,
    n_items: int,
    rank: int,
    gather_dtype: str | None,
    solver: str,
) -> CapacityPlan:
    """Price the chunked host-streamed fallback: only the factor tables stay
    resident, in dispatch order (:func:`stream_table_rows`), with the
    relayouts' index vectors (a position a row each way); one bucket's
    slab, gather block and solve (:func:`chunked_row_bytes` a slot row) is
    in flight at a time. The shapes
    are the planner's at ``batch_size`` rows: the fit itself fills a dispatch
    of one-entry rows up to its side's worst of them and no further, so their
    worst bounds its own. Once a fit each way, a relayout holds the larger
    table in its other order beside both (``relayout_copy``).

    An upper bound, and it stays one: a row is never priced under a
    ``(rank, rank)`` system and one rank-vector beside its slab and block,
    what this rung was admitted at before the solve was priced by what it
    builds. Under CG that holds the short rows' buckets (no system) at the old
    price, so the rung's total does not fall while the chip's peak has not
    (10M x 1M x 100M stars at rank 128: 6,930,038,784 B before and after PR
    34; PERF.md section 6). The relayout's copy and a bucket in flight are
    priced side by side: the host runs ahead, so the relayout back into
    logical order is placed while the last buckets still run - the fit's
    measured peak, 11.94 GB against tables of 5.63 (one v5e, PR 38)."""
    gb = _dtype_bytes(gather_dtype)
    user_rows = stream_table_rows(bucket_shapes_user, n_users)
    item_rows = stream_table_rows(bucket_shapes_item, n_items)

    def row(ln: int) -> int:
        held = bucket_slab_bytes(1, ln) + ln * (rank * gb + gb) + rank * rank * 4 + rank * 4
        return max(chunked_row_bytes(ln, rank, gather_dtype, solver), held)

    worst = max(
        (b * row(ln) for shapes in (bucket_shapes_user, bucket_shapes_item) for b, ln in shapes),
        default=0,
    )
    return CapacityPlan(
        workload="als_fit_chunked",
        items={
            "factor_tables": (user_rows + item_rows) * rank * 4,
            "relayout_rows": (user_rows + n_users + item_rows + n_items) * 4,
            "worst_bucket_in_flight": worst,
            "relayout_copy": max(n_users, n_items) * rank * 4,
        },
    )


def plan_serve(
    n_users: int,
    n_items: int,
    rank: int,
    excl_entries: int = 0,
    generations: int = 1,
    n_devices: int = 1,
) -> CapacityPlan:
    """Price ``generations`` device-resident serving generations, PER
    DEVICE.

    A generation pins both factor tables (``ALSModel.device_factors``) plus
    the -1-padded exclusion table (int32 per entry). During a hot swap TWO
    generations are resident — the incumbent never stops until the candidate
    passes its post-swap checks — which is exactly the pressure the reload
    capacity gate admits against.

    ``n_devices > 1`` prices the mesh-resident serving layout (factor
    tables and the exclusion table row-sharded over the mesh, the PR 8
    layout): each device holds 1/n. This is what makes degraded-mesh
    serving admission honest — after the ladder halves the mesh, the SAME
    artifact's per-device price doubles, and the reload gate must re-judge
    it against the smaller rung rather than the boot-time one.
    """
    n = max(1, int(n_devices))
    per_gen = (_shard_pad(n_users, n) + _shard_pad(n_items, n)) * rank * 4 // n
    return CapacityPlan(
        workload="serve",
        items={
            "factor_tables": per_gen * max(1, generations),
            "exclusion_table": int(excl_entries) * 4 // n,
        },
    )


def plan_foldin(
    bucket: int,
    length: int,
    rank: int,
    n_items: int,
    n_devices: int = 1,
    mode: str = "allgather",
) -> CapacityPlan:
    """Price one fold-in ladder rung, PER DEVICE: the frozen item side
    (factors + Gramian, resident across every batch) plus the rung's padded
    slab and its gathered block.

    ``n_devices > 1`` prices the mesh-resident fold-in (parallel/foldin.py):
    the frozen item table is row-sharded (each device holds 1/n of the
    padded table plus a replicated Gramian), the user slab is routed so each
    shard solves ``bucket // n`` of its own users, and ``mode`` picks the
    source-assembly transient — ``allgather`` materialises the whole padded
    item table per batch, ``ring`` only ever holds two 1/n shards (the
    resident one plus the ppermute'd one in flight). This is the same
    allgather-vs-ring footprint split ``plan_fit_sharded`` prices for
    training, and it is what lets ``admit_ladder`` honestly degrade a
    fold-in batch from allgather to ring when the gather transient is what
    busts the budget.
    """
    n = max(1, int(n_devices))
    i_pad = _shard_pad(n_items, n)
    item_side = i_pad * rank * 4 // n + rank * rank * 4
    slab = bucket * length * (4 + 4 + 1) // n
    b_per = max(1, bucket // n)
    gathered = b_per * length * rank * 4 + b_per * rank * rank * 4
    items = {
        "frozen_item_side": item_side,
        "rung_slab": slab,
        "rung_gather": gathered,
    }
    if n == 1:
        workload = "foldin"
    elif mode == "ring":
        workload = "foldin_sharded_ring"
        # Two source shards in flight: the resident one and the ppermute'd
        # visitor (double-buffered, same as plan_fit_sharded's ring price).
        items["transient_assembly"] = 2 * (i_pad // n) * rank * 4
    else:
        workload = "foldin_sharded"
        items["transient_assembly"] = i_pad * rank * 4
    return CapacityPlan(workload=workload, items=items)


def plan_retrieval(
    tables: "list[tuple[int, int]]",
    excl_entries: int = 0,
    generations: int = 1,
    max_batch: int = 64,
    item_block: int = 4096,
    k: int = 64,
    n_devices: int = 1,
) -> CapacityPlan:
    """Price ``generations`` resident retrieval-bank generations, PER
    DEVICE.

    ``tables``: every table the bank pins — each source's (rows, dim)
    embedding table plus its user-row query table when it has one. During a
    bank hot-swap TWO generations are resident (the incumbent keeps serving
    until the candidate's gates pass), which is what ``generations=2``
    admits against. Transient: one query batch's gathered rows + the
    blocked-MIPS working set (a (B, item_block) score block and the running
    (B, k) top-k) for the widest table.

    ``n_devices > 1`` prices the mesh layout: source tables row-sharded
    over the mesh (``parallel/topk.py`` serves per-shard top-k), so each
    device holds 1/n of the resident tables while the per-batch transient
    stays whole. A bank that fit at 8 shards can genuinely refuse at 4 —
    the degraded-ladder rung doubles each device's share — and that
    refusal stays a recorded non-quarantine rejection.
    """
    n = max(1, int(n_devices))
    resident = sum(_shard_pad(int(rows), n) * int(d) * 4 // n for rows, d in tables)
    max_dim = max((int(d) for _, d in tables), default=0)
    b = max(1, int(max_batch))
    transient = b * max_dim * 4 + b * (int(item_block) + int(k)) * 4
    return CapacityPlan(
        workload="retrieval",
        items={
            "embedding_tables": resident * max(1, int(generations)),
            "exclusion_table": int(excl_entries) * 4,
            "transient_query": transient,
        },
    )


def plan_score(
    tables: "list[tuple[int, int]]",
    shard_users: int,
    k: int = 30,
    max_batch: int = 64,
    item_block: int = 4096,
    n_devices: int = 1,
    streamed: bool = False,
) -> CapacityPlan:
    """Price one batch-scoring sweep configuration, PER DEVICE.

    The ``score_all`` job streams user shards through the retrieval bank's
    blocked MIPS and the LR re-rank; its admission ladder has two rungs
    built from this model:

    - **resident** (``streamed=False``): the whole user shard is one query
      batch — the bank sees ``B = shard_users`` and the blocked-MIPS
      working set scales with it. Fastest when it fits.
    - **streamed** (``streamed=True``): the bank's internal ``max_batch``
      splitting bounds the in-flight batch at ``B = max_batch``; only the
      per-shard top-k landing buffer still scales with the shard. The
      cheap rung for out-of-core catalogs.

    ``tables`` lists every (rows, dim) table the bank pins (source item
    tables + their user query tables), row-sharded over ``n_devices``
    like :func:`plan_retrieval` — a batch job holds ONE generation (no
    hot-swap pressure). Refusal of BOTH rungs is the "before any byte
    moves" contract: :class:`CapacityExceeded` fires at admission, before
    the bank is built or a single shard is read.
    """
    n = max(1, int(n_devices))
    resident = sum(_shard_pad(int(rows), n) * int(d) * 4 // n for rows, d in tables)
    max_dim = max((int(d) for _, d in tables), default=0)
    b = max(1, int(max_batch) if streamed else int(shard_users))
    transient = b * max_dim * 4 + b * (int(item_block) + int(k)) * 4
    # Per-shard top-k landing buffer (scores f32 + rows i32), resident for
    # the shard's lifetime on whichever rung — it is what the spill writes.
    landing = max(1, int(shard_users)) * int(k) * (4 + 4)
    return CapacityPlan(
        workload="score_streamed" if streamed else "score",
        items={
            "bank_tables": resident,
            "transient_query": transient,
            "topk_landing": landing,
        },
    )


def max_foldin_entries(
    rank: int, n_items: int, budget: int | None = None, length: int = 1
) -> int:
    """The largest ``bucket * length`` product whose fold-in rung fits the
    budget — the cap on the pow2 shape ladder, for rungs of the given
    ``length``. Returns at least 1 (a single short row must always be
    dispatchable; if even that OOMs for real, the solve itself will say so).

    Per-entry bytes must cover everything ``plan_foldin`` prices, or a rung
    shrunk to this cap would still admit over-budget: slab (idx+val+mask)
    + gathered rank-vector + the per-SLOT ``(B, rank, rank)`` Gramian
    correction, which amortizes as ``rank^2*4 / length`` per entry. The
    default ``length=1`` is the conservative floor — a caller that knows
    its rung's padded length passes it and gets a proportionally larger
    cap; one that doesn't never under-prices a batch of 1-star rows."""
    budget = budget_bytes() if budget is None else int(budget)
    item_side = n_items * rank * 4 + rank * rank * 4
    per_entry = (4 + 4 + 1) + rank * 4 + (rank * rank * 4) // max(1, int(length))
    spare = budget - item_side
    if spare <= per_entry:
        return 1
    return max(1, int(spare // per_entry))


# --- compiler cross-check -----------------------------------------------------


def compiled_memory_bytes(compiled) -> dict | None:
    """Best-effort read of an AOT executable's own memory analysis.

    Returns ``{argument, output, temp, generated_code, total}`` bytes or
    ``None`` when the backend doesn't expose ``memory_analysis()`` (older
    jaxlib, some CPU builds). Callers use it to cross-check the static model
    — a static estimate wildly below the compiler's own number means the
    model went stale, and the larger figure should drive admission."""
    try:
        ma = compiled.memory_analysis()
        if ma is None:
            return None
        out = {
            "argument": int(getattr(ma, "argument_size_in_bytes", 0)),
            "output": int(getattr(ma, "output_size_in_bytes", 0)),
            "temp": int(getattr(ma, "temp_size_in_bytes", 0)),
            "generated_code": int(getattr(ma, "generated_code_size_in_bytes", 0)),
        }
        alias = int(getattr(ma, "alias_size_in_bytes", 0))
        out["total"] = max(0, sum(out.values()) - alias)
        return out
    except Exception:  # noqa: BLE001 — advisory only
        return None


def cross_check(plan: CapacityPlan, compiled) -> dict | None:
    """Compare the static plan against the compiler's memory analysis.

    Advisory: returns the comparison record (logged when the static model
    underestimates by >2x) or None when no analysis is available."""
    analysis = compiled_memory_bytes(compiled)
    if analysis is None or not analysis.get("total"):
        return None
    static = plan.required_bytes
    ratio = analysis["total"] / max(1, static)
    record = {
        "static_bytes": static,
        "compiled_bytes": analysis["total"],
        "ratio": round(ratio, 3),
        "analysis": analysis,
    }
    # Warn only on MATERIAL underestimates: tiny programs carry fixed XLA
    # temp overheads that dwarf their slabs (ratio noise at KB scale), and
    # a model off by a few hundred KB cannot mis-admit anything.
    if ratio > 2.0 and analysis["total"] - static > 64 << 20:
        log.warning(
            "capacity model underestimates %s: static %s bytes vs compiler "
            "%s bytes (%.1fx) — admission should trust the larger figure",
            plan.workload, f"{static:,}", f"{analysis['total']:,}", ratio,
        )
    return record


def bucket_plan_shapes(indptr: np.ndarray, **layout_kwargs) -> list[tuple[int, int]]:
    """Shapes ``(B, L)`` the bucket planner would allocate for this CSR/CSC
    side — the pricing input, computed WITHOUT filling any slab."""
    from albedo_tpu.datasets.ragged import plan_buckets

    return [p.shape for p in plan_buckets(indptr, **layout_kwargs)]


def counts_indptr(row_ids: np.ndarray, n_rows: int) -> np.ndarray:
    """An indptr from bare row ids — all the planner needs. Pricing must
    not pay the O(nnz log nnz) argsort a full ``matrix.csr()``/``csc()``
    view costs just to read row lengths (the cold path sorts them again
    for real minutes later)."""
    counts = np.bincount(np.asarray(row_ids), minlength=n_rows)
    indptr = np.zeros(n_rows + 1, dtype=np.int64)
    np.cumsum(counts, out=indptr[1:])
    return indptr

"""SubmissionPipeline — the explicit, lock-protected submission path.

Every computational element enters the runtime through this object.
Multi-tenant serving has *concurrent* submitters, so one re-entrant lock
guards every stage:

    place -> reserve (EVICT) -> reload -> prefetch (H2D) -> migrate (D2D)
          -> schedule (DAG, lane, executor)

The lock is held across the whole pipeline for one element (plus the host
synchronization paths), which keeps the paper's dependency inference sound
under concurrency: the DAG frontier, the stream manager's lane table and the
executor's clocks are only ever mutated by the lock holder.  Submissions from
different threads serialize at the pipeline; the *executors* still overlap
device work freely (that is the whole point of lanes).

The last stage, :meth:`SubmissionPipeline.schedule`, admits every element,
eager or replayed from a captured plan (capture.py), into the DAG, a lane
and the executor.
"""
from __future__ import annotations

import itertools
import threading
from typing import (TYPE_CHECKING, Iterable, List, NamedTuple, Optional,
                    Sequence, Set, Tuple)

from .element import (Arg, ComputationalElement, DEFAULT_TENANT, ElementKind,
                      inout)
from .timeline import span

if TYPE_CHECKING:  # pragma: no cover
    from .scheduler import GrScheduler
    from .streams import Lane


class Placement(NamedTuple):
    """A replayed element's place, resolved by its plan: its lane, its wait
    events, and the args whose live frontier it also orders behind."""

    lane: "Lane"
    waits: List[ComputationalElement]
    entry: Tuple[Arg, ...]


class SubmissionPipeline:
    """Serializes concurrent submitters onto one scheduler instance."""

    def __init__(self, sched: "GrScheduler") -> None:
        self.sched = sched
        # RLock: host-access synchronization can nest inside a launch (e.g.
        # a ManagedValue.get() issued from a tuning callback) and the public
        # entry points wrap each other freely.
        self._lock = threading.RLock()
        self.submissions = 0
        self._seen_threads: Set[int] = set()

    # -- critical section ------------------------------------------------
    def __enter__(self) -> "SubmissionPipeline":
        with span("lock"):
            self._lock.acquire()
        self._seen_threads.add(threading.get_ident())
        return self

    def __exit__(self, exc_type, exc, tb) -> bool:
        self._lock.release()
        return False

    # -- stages ----------------------------------------------------------
    def run(self, e: ComputationalElement) -> None:
        """Full pipeline for a kernel element under the parallel policy.

        Caller must hold the pipeline lock (``with sched.pipeline:``)."""
        sched = self.sched
        # Placement first: prefetches land on the consuming device and
        # cross-device inputs get D2D copies before the kernel is added.
        # A caller-pinned device (GrFunction ``with_options(device=...)``)
        # bypasses the placement policy but is clamped to the device count.
        with span("place"):
            if e.device is None:
                e.device = sched.streams.place(e, sched.executor.is_done)
            else:
                e.device = min(max(0, int(e.device)), sched.num_devices - 1)
        # Reserve the element's working set before anything lands on the
        # device: under budget pressure this synthesizes DAG-ordered EVICT
        # elements for LRU victims (spill D2H first, reload H2D after — the
        # copy engines see them in that order).
        with span("reserve"):
            self.reserve(e)
        # Tier-resident read args (spilled to compressed host / disk) must
        # come back regardless of ``auto_prefetch``: the fault-driven
        # single-device mode can read a *host-valid* array in place, but a
        # tier payload is not host-addressable until its RELOAD runs.
        self.reload(e.args, e.device, priority=e.priority, tenant=e.tenant,
                    deadline_s=e.deadline_s, deadline_t=e.deadline_t)
        # Host-resident read args must reach the device ahead of the kernel.
        # With auto_prefetch off on a single device the executor reads the
        # host copy in place (GrCUDA's fault-driven mode), but on multiple
        # devices skipping the H2D would leave cross-device host-only reads
        # never localized (migrate() only moves device-owned copies), so the
        # upload is forced regardless of the flag.
        if sched.auto_prefetch or sched.num_devices > 1:
            with span("prefetch"):
                self.prefetch(e.args, e.device, priority=e.priority,
                              tenant=e.tenant, deadline_s=e.deadline_s,
                              deadline_t=e.deadline_t)
        if sched.num_devices > 1:
            with span("migrate"):
                self.migrate(e.args, e.device, priority=e.priority,
                             tenant=e.tenant, deadline_s=e.deadline_s,
                             deadline_t=e.deadline_t)
        self.schedule([(e, None)])

    def reserve(self, e: ComputationalElement,
                extra_pinned: Optional[Iterable[int]] = None) -> None:
        """Budget stage: make room for ``e``'s working set on its device.

        No-op under unlimited budgets.  Victims are chosen LRU-first among
        non-frontier arrays (no live DAG readers/writer); each victim gets
        one EVICT element — an async D2H write-back (clean copies just
        drop) ordered *after* the victim's last reader by the ordinary
        dependency rules, exactly like the paper's transparent H2D/D2D
        insertion.  Evictions inherit the triggering element's priority,
        tenant and deadline (stamped here first): making room is work done
        on that element's behalf.

        ``extra_pinned`` forwards to :meth:`MemoryManager.reserve` — a
        replay pins its plan-bound arrays so only foreign leftovers are
        evicted under a replay."""
        sched = self.sched
        mem = sched.memory
        if not mem.bounded:
            return
        sched.deadlines.tag(e)
        for ma in mem.reserve(e.device, e, sched.dag.has_device_frontier,
                              extra_pinned):
            self.evict(ma, priority=e.priority, tenant=e.tenant,
                       deadline_s=e.deadline_s, deadline_t=e.deadline_t)

    def reserve_plan(self, plan, extra_pinned: Optional[Iterable[int]] = None
                     ) -> None:
        """Budget stage for a Belady-scheduled plan replay: make room for
        the plan's recorded per-device peaks once, up front.

        A ``mem_scheduled`` plan carries its own EVICT elements — its
        element order *is* the memory schedule — so the only possible
        victims here are *foreign* leftovers from earlier episodes still
        holding bytes the plan's peak needs.  Plan gating
        (``plan_fits``) already guaranteed peak <= budget."""
        sched = self.sched
        mem = sched.memory
        if not mem.bounded:
            return
        for device, peak in plan.device_mem:
            for ma in mem.reserve_bytes(device, peak,
                                        sched.dag.has_device_frontier,
                                        extra_pinned):
                self.evict(ma)

    def evict(self, ma, *, priority: int = 0,
              tenant: str = DEFAULT_TENANT,
              deadline_s: Optional[float] = None,
              deadline_t: Optional[float] = None) -> ComputationalElement:
        """Synthesize and schedule one EVICT element for ``ma``.

        ``inout`` access makes the DAG order it after every in-flight
        reader and the last writer of the array; the device copy is dropped
        at schedule time (logical bits + residency via the MemoryManager),
        the executors perform the physical write-back/release.  A clean
        copy (host still valid) is dropped without moving bytes.

        Dirty victims consult the spill-tier stack (``memory.select_tier``):
        a peer-device spill becomes a device-to-device transfer (the EVICT
        runs on the D2D link, ``src_device`` set like any D2D element), a
        host-tier spill stays on the D2H engine but the tier stores/encodes
        the payload instead of the plain host write-back.  A stack-wide
        miss — or no stack at all — is the flat PR 5 D2H spill."""
        dirty = not getattr(ma, "host_valid", True)
        tier, plan = (self.sched.memory.select_tier(ma) if dirty
                      else (None, None))
        plan = plan or {}
        t = ComputationalElement(
            fn=None, args=(inout(ma),), kind=ElementKind.EVICT,
            name=f"evict_{ma.name}",
            transfer_bytes=(int(plan.get("transfer_bytes", ma.nbytes))
                            if dirty else 0),
            config=dict({"writeback": dirty}, **plan.get("config", {})),
            priority=priority, tenant=tenant,
            deadline_s=deadline_s, deadline_t=deadline_t)
        t.tier = tier
        t.device = ma.device_id if ma.device_id is not None else 0
        if tier is not None and tier.location == "device":
            # Runs on the (source -> target) D2D link.
            t.device, t.src_device = plan.get("target"), t.device
        self.schedule([(t, None)])
        return t

    def reload(self, args: Sequence[Arg], device: int, *,
               priority: int = 0, tenant: str = DEFAULT_TENANT,
               deadline_s: Optional[float] = None,
               deadline_t: Optional[float] = None) -> None:
        """Insert RELOAD elements for read args parked in a host-side tier
        (``ma.backing_tier`` set).  The tier handler restores the host
        payload and the H2D engine uploads it; the DAG orders the RELOAD
        after the spill's write via the ordinary ``inout`` rules.  Peer-tier
        blocks never reach here — they are device-resident and come back
        through the migrate stage's plain D2D."""
        sched = self.sched
        for a in args:
            ma = a.array
            tname = getattr(ma, "backing_tier", None)
            if tname is None or not a.mode.reads:
                continue
            tier = sched.memory.tier_named(tname)
            if tier is None:        # stack reconfigured under a live block
                continue
            cfg = {"tier": tier.name}
            gbps = getattr(tier, "gbps", None)
            if gbps is not None:
                cfg["tier_gbps"] = gbps
            t = ComputationalElement(
                fn=None, args=(inout(ma),), kind=ElementKind.RELOAD,
                name=f"reload_{ma.name}",
                transfer_bytes=tier.reload_wire_bytes(ma),
                config=cfg, priority=priority, tenant=tenant,
                deadline_s=deadline_s, deadline_t=deadline_t)
            t.tier = tier
            t.device = device
            self.schedule([(t, None)])

    def prefetch(self, args: Sequence[Arg], device: int = 0, *,
                 priority: int = 0, tenant: str = DEFAULT_TENANT,
                 deadline_s: Optional[float] = None,
                 deadline_t: Optional[float] = None) -> None:
        """Insert asynchronous H2D transfers for host-resident read args.

        The transfers inherit the consuming kernel's priority/tenant (and
        deadline): a latency-critical kernel's input upload must not be
        accounted (or de-prioritized) as someone else's work."""
        for a in args:
            ma = a.array
            if a.mode.reads and ma.host_valid and not ma.device_valid:
                t = ComputationalElement(
                    fn=None, args=(inout(ma),), kind=ElementKind.TRANSFER,
                    name=f"h2d_{ma.name}", transfer_bytes=ma.nbytes,
                    priority=priority, tenant=tenant,
                    deadline_s=deadline_s, deadline_t=deadline_t)
                t.device = device
                self.schedule([(t, None)])

    def migrate(self, args: Sequence[Arg], device: int, *,
                priority: int = 0, tenant: str = DEFAULT_TENANT,
                deadline_s: Optional[float] = None,
                deadline_t: Optional[float] = None) -> None:
        """Move device-resident read args owned by *other* devices onto
        ``device`` via D2D transfer elements (single-copy ownership model:
        the copy migrates, it is not replicated)."""
        for a in args:
            ma = a.array
            if not a.mode.reads or not getattr(ma, "device_valid", False):
                continue
            src = getattr(ma, "device_id", None)
            if src is None:
                # Claim an unowned copy: no element, no bytes moved.
                self.sched.memory.note_d2d(ma, device)
                continue
            if src == device:
                continue
            t = ComputationalElement(
                fn=None, args=(inout(ma),), kind=ElementKind.D2D,
                name=f"d2d_{ma.name}", transfer_bytes=getattr(ma, "nbytes", 0),
                priority=priority, tenant=tenant,
                deadline_s=deadline_s, deadline_t=deadline_t)
            t.device = device
            t.src_device = src
            self.schedule([(t, None)])

    def schedule(self, items: Iterable[
            Tuple[ComputationalElement, Optional[Placement]]]) -> None:
        """Admission stage: the one way an element enters the DAG, a lane
        and the executor.

        Each item is ``(element, placement)``.  An eager element (None)
        costs one launch overhead and gets inferred parents (``dag.add``)
        and a lane (``streams.assign``); a replayed one brings its plan
        parents on ``element.parents`` and a :class:`Placement`
        (``dag.adopt``, ``streams.bind_to_lane``); under the serial policy
        each element runs alone on lane 0.  ``items`` may be lazy: each
        element is admitted before the next is drawn, so a replay's
        per-element reserve sees the location bits before it.  Then one
        executor submission for them all (the serial policy waits for it)
        and one deadline-risk check, on the last."""
        sched = self.sched
        ex = sched.executor
        serial = sched.policy != "parallel"
        batch = []
        for e, placed in items:
            with span("schedule", uid=e.uid) as sp:
                # Idempotent: kernels arrive tagged from _launch and
                # transfer children with an inherited deadline_t.
                sched.deadlines.tag(e)
                if placed is None:
                    ex.host_overhead(sched.launch_overhead_s)
                if sched.sanitizer is not None:
                    sched.sanitizer.on_schedule(e)
                if serial:
                    e.parents, waits = [], []
                elif placed is None:
                    sched.dag.add(e)
                    _, waits = sched.streams.assign(e, ex.is_done)
                else:
                    entry = sched.dag.adopt(e, placed.entry)
                    sched.streams.bind_to_lane(placed.lane, e)
                    waits = [w for w in itertools.chain(placed.waits, entry)
                             if not ex.is_done(w)]
                lane_id = e.stream if e.stream is not None else 0
                sp.set_metadata(lane=lane_id)
                if e.kind is ElementKind.KERNEL:
                    sched.read_ahead.on_launch(e)
                if sched._capture is not None:
                    # Before the location bits: a recorded slot keeps the
                    # state its array had at first use.
                    sched._capture.trace(e)
                self._note_location(e, planned=placed is not None)
                sched._elements.append(e)
                self.submissions += 1
                batch.append((e, lane_id, waits))
        ex.submit(batch)
        if serial:
            for e, _, _ in batch:
                ex.wait(e)
        sched.deadlines.on_submit(batch[-1][0])

    def _note_location(self, e: ComputationalElement, planned: bool) -> None:
        """Flip the location bits an element implies, by its kind, through
        the MemoryManager so residency tracks them.  A ``planned`` EVICT is
        part of a plan's (maybe Belady) memory schedule, not reactive."""
        sched = self.sched
        mem = sched.memory
        ma = e.args[0].array if e.args else None
        dev = e.device if e.device is not None else 0
        if e.src_device is not None:
            sched.d2d_transfers += 1    # D2D copies and peer-tier spills
        if e.kind is ElementKind.TRANSFER:
            mem.note_h2d(ma, dev)
        elif e.kind is ElementKind.D2D:
            mem.note_d2d(ma, dev)
        elif e.kind is ElementKind.RELOAD:
            mem.note_reload(ma, dev)
        elif e.kind is ElementKind.EVICT:
            if e.tier is not None:
                mem.note_spill(ma, e.tier, e.config.get("spill_target"),
                               e.transfer_bytes, scheduled=planned)
            else:
                mem.note_evict(ma, scheduled=planned)
        else:
            for a in e.args:
                if a.mode.writes:
                    mem.note_device_write(a.array, dev)

    # -- stats -----------------------------------------------------------
    def stats(self) -> dict:
        return {"pipeline_submissions": self.submissions,
                "pipeline_threads_seen": len(self._seen_threads)}

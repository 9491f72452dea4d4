"""Unified execution sessions — ONE executor behind the three Pipes.

The paper's contribution is a single persistent-worklist Pipe whose
dispatch regime varies per iteration. A ``Session`` owns ONE keyed
compile cache for every regime (DESIGN.md §9):

  * ``Session.run(spec, g)`` executes an ``ExecutionSpec`` (spec.py) in
    its declared regime — host loop, device-resident outlined chunks, or
    the sharded Pipe — reusing every prepared/compiled artifact the
    session has seen for the same ``spec.static_key() x graph`` pair. It
    is the one way to color a graph.
  * ``Session.run_batch(spec, graphs)`` colors MANY graphs in one device
    dispatch (exec/batch.py): graphs are padded into shape-class buckets
    and the step runs ``vmap``-ed over lanes inside a single
    ``lax.while_loop`` until every lane drains — the serving-scale
    workload the unified cache exists for.
  * ``Session.stats`` counts cache hits/misses so warm-vs-cold behaviour
    is observable, and the distinct shape classes of the graphs it
    prepared (``ipgc.signature``): graphs of one class share every
    compiled step.

Cache-key discipline: an entry is keyed on the spec's static fields plus
the graph's identity (``id(g)`` + static shape fields — the entry pins
the graph object, so ids cannot be recycled while the entry lives).
Prepare entries are shared across the host and outlined regimes (same
prepared ``IPGCGraph``); distributed entries carry the partitioned graph
and the shard_map'd step closures, keyed by the graph's content. A
caller that wants its own store passes it as ``Session(cache=d)``.
"""
from __future__ import annotations

import contextlib
import dataclasses
import math
import threading
import time
import zlib

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import ipgc
from repro.core.policy import (AutoTuned, Policy, Timer, adaptive_window,
                               device_threshold, exchange_threshold,
                               make_policy, measure_launches)
from repro.core.result import ColoringResult
from repro.core.worklist import (bucket_capacities, chunk_lower_bounds,
                                 pick_bucket, resize_items)
from repro.exec.spec import ExecutionSpec
from repro.graphs.csr import Graph
from repro.graphs.layout import resolve_plan
from repro.kernels.tune import resolve_tile_rows
from repro.obs import trace as obs_trace
from repro.obs.report import (RunReport, dense_exchange_bytes,
                              dense_swap_bytes, exchange_section,
                              packed_exchange_bytes, totals_from_trace)


@dataclasses.dataclass
class CacheStats:
    """Hit/miss counters for the session's unified compile cache, and the
    distinct shape classes of the graphs it prepared."""

    hits: int = 0
    misses: int = 0
    evictions: int = 0
    #: ``ipgc.signature`` of every graph the session prepared
    classes: set = dataclasses.field(default_factory=set)

    @property
    def shape_classes(self) -> int:
        return len(self.classes)

    @property
    def hit_rate(self) -> float:
        total = self.hits + self.misses
        return self.hits / total if total else 0.0

    def as_dict(self) -> dict:
        return {"hits": self.hits, "misses": self.misses,
                "evictions": self.evictions,
                "hit_rate": round(self.hit_rate, 4),
                "shape_classes": self.shape_classes}

    def reset(self) -> None:
        self.hits = 0
        self.misses = 0
        self.evictions = 0
        self.classes = set()


@dataclasses.dataclass
class _DispatchMeter:
    """Per-run device-dispatch accounting, filled by the drivers when a
    run is traced (DESIGN.md §12). ``statics`` snapshots the driver's
    resolved static arguments so the work profiler replays exactly the
    resolution the run used.
    """

    dispatch_seconds: float = 0.0
    n: int = 0
    statics: "dict | None" = None

    def add(self, seconds: float) -> None:
        self.dispatch_seconds += seconds
        self.n += 1

    def timing(self, total_seconds: float) -> dict:
        return {"total_seconds": total_seconds,
                "dispatch_seconds": self.dispatch_seconds,
                "dispatches": self.n}


def _graph_key(g) -> tuple:
    """Graph half of the unified cache key: identity + static fields.

    ``id(g)`` disambiguates same-named graphs; every cache entry stores a
    reference to ``g``, so the id cannot be recycled while it is live.
    """
    if isinstance(g, Graph):
        return ("graph", id(g), g.name, g.n_nodes, g.n_edges)
    return ("ig", id(g), g.n_nodes, g.ell_width, g.n_hub, g.layout_kind)


def _class_attrs(g, ig) -> dict:
    """``session.prepare``'s attributes: the seed-dependent sizes of the
    prepared graph on its shape class, and, where ``g`` is the host Graph
    it came from, the slots the class added over those it had before
    (``pad_share``, a fraction)."""
    sizes = ipgc.class_sizes(ig)
    attrs = {"shape_class": sizes}
    if isinstance(g, Graph):
        exact = ipgc.exact_sizes(g, ig.layout_kind)
        attrs["pad_share"] = sum(sizes.values()) / sum(exact.values()) - 1
    return attrs


def _csr_checksum(g: Graph) -> int:
    """CRC32 of the graph's CSR arrays (content identity)."""
    a = g.arrays
    crc = zlib.crc32(np.ascontiguousarray(a.row_ptr).data)
    return zlib.crc32(np.ascontiguousarray(a.col_idx).data, crc)


class Session:
    """One keyed compile cache + driver loops for all dispatch regimes.

    ``max_entries`` bounds the cache FIFO-style (oldest entry evicted
    first): entries pin their graph objects, so an unbounded session
    serving an endless stream of *distinct* graphs would grow without
    limit. ``None`` (the default for explicitly-constructed sessions)
    keeps every entry; ``default_session()`` — the process-wide store —
    is bounded.
    """

    def __init__(self, cache: dict | None = None,
                 max_entries: int | None = None):
        #: the unified cache; a caller-supplied dict becomes the
        #: backing store (it then outlives the session)
        self.cache: dict = {} if cache is None else cache
        self.max_entries = max_entries
        self.stats = CacheStats()
        self._pin_depth = 0
        self._pinned: set = set()
        #: reentrant guard for the cache/pin/evict triplet — a session
        #: shared with an async stream front-end (serve/stream.py) sees
        #: lookups from more than one thread; reentrancy keeps nested
        #: ``cached`` calls inside a ``build`` legal
        self._lock = threading.RLock()

    @contextlib.contextmanager
    def pin(self):
        """Exempt every entry touched inside the block from FIFO eviction.

        A multi-entry run (``run_batch``, a streaming round) touches
        several cache entries that must stay live TOGETHER for its whole
        duration — on a bounded session, a long run over many distinct
        shape classes could otherwise evict its own earlier entries
        mid-flight (the live stacked batch, the pad entries its lanes
        share). While pinned the bound may be exceeded; the outermost
        exit re-applies it against the then-oldest unpinned entries.
        Nests: inner pins extend the outermost scope.
        """
        with self._lock:
            self._pin_depth += 1
        try:
            yield self
        finally:
            with self._lock:
                self._pin_depth -= 1
                if self._pin_depth == 0:
                    self._pinned.clear()
                    self._evict()

    def _evict(self) -> None:
        if self.max_entries is None:
            return
        with self._lock:
            while len(self.cache) > self.max_entries:
                # FIFO eviction: dicts preserve insertion order and the
                # entry just added is last, so it never evicts itself;
                # pinned keys (a live run's own entries) are skipped
                victim = next(
                    (k for k in self.cache if k not in self._pinned),
                    None)
                if victim is None:
                    return
                self.cache.pop(victim)
                self.stats.evictions += 1

    def cached(self, key: tuple, build):
        """Single lookup point — every compiled/prepared artifact in every
        regime goes through here, so ``stats`` reflects true reuse."""
        with self._lock:
            try:
                entry = self.cache[key]
            except KeyError:
                self.stats.misses += 1
                entry = self.cache[key] = build()
                if self._pin_depth > 0:
                    self._pinned.add(key)
                self._evict()
                return entry
            if self._pin_depth > 0:
                self._pinned.add(key)
            self.stats.hits += 1
            return entry

    def _note_class(self, ig) -> None:
        """Count ``ig``'s shape class among those the session prepared."""
        sig = ipgc.signature(ig)
        with self._lock:
            self.stats.classes.add(sig)

    # -- public API ----------------------------------------------------------

    def run(self, spec: ExecutionSpec, g, *, policy: Policy | None = None,
            collect_tti: bool = False, mesh=None,
            node_axes: tuple = ("data",), trace=None):
        """Execute ``spec`` on one graph in its declared regime.

        ``trace`` turns on telemetry (DESIGN.md §12): pass ``True`` for
        a fresh ``obs.Trace``, or a ``Trace`` instance to append to one
        (e.g. with an injected clock). A traced run returns a
        ``RunReport`` — the same ``ColoringResult`` (under ``.result``,
        with passthrough properties) PLUS span timings, per-iteration
        launch/gather/exchange profiles, the compile-vs-execute split
        and a cache snapshot. Telemetry is host-side only: the traced
        run's jaxprs — and therefore its colors — are bit-identical to
        the untraced run's (tests/test_obs.py).
        """
        if trace is None or trace is False:
            return self._execute(spec, g, policy=policy,
                                 collect_tti=collect_tti, mesh=mesh,
                                 node_axes=node_axes)
        tr = obs_trace.Trace() if trace is True else trace
        meter = _DispatchMeter()
        stats0 = dataclasses.replace(self.stats)
        with obs_trace.tracing(tr):
            with tr.span("session.run", regime=spec.regime, mode=spec.mode,
                         algo=str(spec.algo), graph=self._graph_name(g)):
                result = self._execute(spec, g, policy=policy,
                                       collect_tti=collect_tti, mesh=mesh,
                                       node_axes=node_axes, meter=meter)
                with tr.span("obs.profile"):
                    profile = self._work_profile(meter)
        return self._assemble_report(spec, g, result, meter, profile,
                                     stats0, tr)

    def _execute(self, spec: ExecutionSpec, g, *, policy, collect_tti,
                 mesh, node_axes, meter=None) -> ColoringResult:
        if spec.regime == "dist":
            return self._run_dist(spec, g, policy=policy,
                                  collect_tti=collect_tti, mesh=mesh,
                                  node_axes=node_axes, meter=meter)
        if spec.regime == "outlined":
            return self._run_outlined(spec, g, policy=policy,
                                      collect_tti=collect_tti, meter=meter)
        return self._run_host(spec, g, policy=policy,
                              collect_tti=collect_tti, meter=meter)

    @staticmethod
    def _graph_name(g) -> str:
        name = getattr(g, "name", None)
        return name if name else f"<prepared n={g.n_nodes}>"

    def run_batch(self, spec: ExecutionSpec, graphs,
                  *, map_to_original: bool = False, trace=None):
        """Color MANY graphs in one (or few) device dispatches.

        See exec/batch.py for the shape-class bucketing contract; results
        come back in input order, bit-identical to ``run(spec_host, g)``
        per graph (spec_host = the same spec in the host regime).
        ``map_to_original=True`` additionally maps each lane's colors
        back through its graph's ``Permutation`` (reordered pipelines).

        With ``trace`` (True or a ``Trace``), returns a batch-level
        ``RunReport`` instead: ``.result`` holds the per-graph result
        list, ``extra["lanes"]`` the per-lane summaries, and the trace
        records one ``batch.dispatch`` span per shape-class bucket.
        """
        from repro.exec import batch as _batch
        if trace is None or trace is False:
            return _batch.run_batch(self, spec, graphs,
                                    map_to_original=map_to_original)
        tr = obs_trace.Trace() if trace is True else trace
        stats0 = dataclasses.replace(self.stats)
        graphs = list(graphs)
        with obs_trace.tracing(tr):
            with tr.span("batch.run", graphs=len(graphs)) as sp:
                results = _batch.run_batch(
                    self, spec, graphs, map_to_original=map_to_original)
        total = sp.seconds if sp.seconds is not None else 0.0
        lanes = [{"graph": self._graph_name(g), "n_nodes": g.n_nodes,
                  "n_colors": r.n_colors, "iterations": r.iterations,
                  "mode_trace": r.mode_trace}
                 for g, r in zip(graphs, results)]
        return RunReport(
            regime="batch", algo=str(spec.algo), graph=f"<{len(graphs)}>",
            n_nodes=sum(g.n_nodes for g in graphs),
            n_colors=max((r.n_colors for r in results), default=0),
            iterations=max((r.iterations for r in results), default=0),
            host_dispatches=len(tr.find("batch.dispatch")),
            timing={"total_seconds": total},
            cache=self._cache_section(stats0),
            result=results, trace=tr, extra={"lanes": lanes})

    def stream(self, spec: ExecutionSpec, config=None):
        """A continuous-batching service over this session's cache.

        Returns a ``StreamSession`` (serve/stream.py): submit requests as
        they arrive, lanes that drain at a chunk boundary are refilled
        from the queue, results are bit-identical to solo ``run`` per
        request (DESIGN.md §11).
        """
        from repro.serve.stream import StreamSession
        return StreamSession(self, spec, config)

    # -- telemetry: work profiling + report assembly (DESIGN.md §12) ---------

    def _work_profile(self, meter: _DispatchMeter) -> dict:
        """Per-iteration device-work profile of the run's resolved steps.

        Measured exactly like the test suites measure it: the step impls
        are traced with ``jax.eval_shape`` (no device execution) under
        the reset-scoped counter groups, so the numbers match
        ``measure_launches`` / the exchange-invariant tests bit-for-bit.
        Cached under the session key space — repeated traced runs of the
        same configuration pay a dict lookup, which keeps a traced run's
        wall time close to an untraced one's.
        """
        s = meter.statics
        if s is None:
            return {}
        if s["kind"] == "dist":
            return self._profile_dist(s)
        alg, ig = s["alg"], s["ig"]
        kw = dict(window=s["window"], impl=s["impl"],
                  force_hub=s["force_hub"], tile_rows=s["tile_rows"])
        key = ("obs-profile", "local", _graph_key(ig), alg, s["fused"],
               tuple(sorted(kw.items())))

        def build():
            colors, aux, wl = alg.init_state(ig)
            out = {}
            for mode, impl_fn in zip(("dense", "sparse"),
                                     alg.step_impls(s["fused"])):
                with ipgc.GATHER_COUNTS.scope() as gc:
                    launches = measure_launches(impl_fn, ig, colors, aux,
                                                wl, **kw)
                    gathers = gc.as_dict()
                out[mode] = {"launches": launches, "gathers": gathers}
            return out

        return self.cached(key, build)

    def _profile_dist(self, s: dict) -> dict:
        """Launch/gather/exchange profile of the distributed steps (one
        ``jax.eval_shape`` per mode — the exchange-invariant measurement
        of tests/test_distributed.py, verbatim).

        The steps are REBUILT for the measurement instead of reusing the
        run's cached closures: a jit function only runs its Python body
        (where the trace-time counters live) on its first trace, and the
        run has already traced the cached ones. Fresh closures make
        ``eval_shape`` re-trace; the profile itself is cached, so the
        cost is one abstract trace per configuration.
        """
        from repro.core import distributed

        ig = s["ig"]
        key = ("obs-profile",) + s["dist_key"]

        def build():
            dense_fn, sparse_fn = s["alg"].make_dist_steps(
                ig, s["mesh"], s["node_axes"], window=s["window"],
                fused=s["fused"], exchange=s["exchange"],
                boundary=s["binfo"], thresh=s["thresh"])
            colors, base, wl = s["alg"].init_state(ig)
            bnd = s["exchange"] != "dense"
            if bnd:
                colors = jnp.broadcast_to(colors,
                                          (s["n_shards"],) + colors.shape)
                bcap0 = s["binfo"].capacities[0]
            out = {}
            for mode, fn in (("dense", dense_fn), ("sparse", sparse_fn)):
                with ipgc.LAUNCH_COUNTS.scope() as lc, \
                        ipgc.GATHER_COUNTS.scope() as gc, \
                        distributed.EXCHANGE_COUNTS.scope() as ec:
                    if bnd:
                        # eval_shape can't carry the static int kwarg
                        jax.eval_shape(lambda c, b, w: fn(c, b, w,
                                                          bcap=bcap0),
                                       colors, base, wl)
                    else:
                        jax.eval_shape(fn, colors, base, wl)
                    out[mode] = {"launches": lc.as_dict(),
                                 "gathers": gc.as_dict(),
                                 "exchanges": ec.as_dict()}
            return out

        return self.cached(key, build)

    def _cache_section(self, stats0: CacheStats) -> dict:
        """Session cache totals + this run's delta."""
        return {**self.stats.as_dict(),
                "run_delta": {
                    "hits": self.stats.hits - stats0.hits,
                    "misses": self.stats.misses - stats0.misses,
                    "evictions": self.stats.evictions - stats0.evictions}}

    def _assemble_report(self, spec, g, result, meter, profile, stats0,
                         tr) -> RunReport:
        def section(field):
            per_iter = {m: profile[m][field] for m in profile}
            return {"per_iter": per_iter,
                    "total": totals_from_trace(result.mode_trace, per_iter)}

        exchanges = None
        if spec.regime == "dist" and profile:
            per_iter = {m: {k: v for k, v in profile[m]["exchanges"].items()
                            if v} for m in profile}
            # byte formulas run over the PARTITIONED node count
            # (prepare_partition pads n to a multiple of the shard
            # count), not the caller's original n_nodes
            exchanges = exchange_section(
                per_iter, meter.statics["ig"].n_nodes, result.mode_trace,
                exchange=meter.statics.get("exchange", "dense"),
                n_shards=meter.statics.get("n_shards", 1),
                exchange_trace=result.exchange_trace,
                exchange_bytes=result.exchange_bytes)
        alg = spec.resolved_algo()
        return RunReport(
            regime=spec.regime, algo=alg.name, graph=self._graph_name(g),
            n_nodes=g.n_nodes, n_colors=result.n_colors,
            iterations=result.iterations, mode_trace=result.mode_trace,
            host_dispatches=result.host_dispatches,
            counts=list(result.counts),
            timing=meter.timing(result.total_seconds),
            launches=section("launches") if profile else {},
            gathers=section("gathers") if profile else {},
            exchanges=exchanges, cache=self._cache_section(stats0),
            result=result, trace=tr)

    # -- shared preparation --------------------------------------------------

    def _prepare(self, spec: ExecutionSpec, g, alg):
        """(graph ref, prepared IPGCGraph, resolved window) — cached, and
        shared between the host and outlined regimes (the prepared graph
        does not depend on the dispatch regime)."""
        if isinstance(g, ipgc.IPGCGraph):
            # already prepared by the caller; only the window resolves
            # (auto needs the host Graph's degrees)
            window = spec.window
            if window == "auto":
                assert not alg.uses_window, \
                    "window='auto' needs a host Graph for this algorithm"
                window = 128
            return g, g, window
        plan = resolve_plan(g, spec.layout)
        key = ("prep", _graph_key(g), alg, spec.priority, plan, spec.window)

        def build():
            if spec.window == "auto":
                window = adaptive_window(g) if alg.uses_window else 128
            else:
                window = spec.window
            ig = alg.prepare(g, priority=spec.priority, plan=plan)
            self._note_class(ig)
            return g, ig, window

        return self.cached(key, build)

    # -- host-loop Pipe (the regime of the seed engine) ----------------------

    def _run_host(self, spec: ExecutionSpec, g, *, policy, collect_tti,
                  meter=None) -> ColoringResult:
        alg = spec.resolved_algo()
        fused = alg.resolve_fused(spec.fused, default=False)
        with obs_trace.maybe_span("session.prepare") as sp:
            _, ig, window = self._prepare(spec, g, alg)
            if sp is not None:
                sp.attrs.update(_class_attrs(g, ig))
        n = ig.n_nodes
        pol = policy or make_policy(spec.mode, spec.h)
        caps = bucket_capacities(n, ratio=spec.bucket_ratio)
        # the hub side-channel runs only where the graph has hubs
        force_hub = False
        tile_rows = resolve_tile_rows(spec.tile_rows, ig.layout_kind,
                                      spec.impl)
        dense_fn, sparse_fn = alg.step_fns(fused)
        if meter is not None:
            meter.statics = dict(kind="host", alg=alg, ig=ig, fused=fused,
                                 window=window, impl=spec.impl,
                                 force_hub=force_hub, tile_rows=tile_rows)

        colors, aux, wl = alg.init_state(ig)
        count = n

        trace: list[str] = []
        counts: list[int] = []
        tti: list[float] = []
        # per mode, the live entries and the slots of each step
        entries: dict[str, list[int]] = {"D": [], "S": []}
        slots: dict[str, list[int]] = {"D": [], "S": []}
        d_slots = alg.dense_slots(ig, force_hub)
        t_start = time.perf_counter()
        it = 0
        while count > 0 and it < spec.max_iter:
            use_dense = bool(pol(count, n))
            mode = "D" if use_dense else "S"
            counts.append(count)
            with obs_trace.maybe_span("session.iter", mode=mode,
                                      count=count), Timer() as t:
                if use_dense:
                    step_fn = dense_fn
                else:
                    step_fn = sparse_fn
                    cap = pick_bucket(caps, count)
                    if wl.capacity > cap:
                        with obs_trace.maybe_span("session.resize"):
                            wl = resize_items(wl, cap, n)
                capacity = wl.capacity
                with obs_trace.maybe_span("session.dispatch"):
                    colors, aux, wl, tally = step_fn(
                        ig, colors, aux, wl, window=window, impl=spec.impl,
                        force_hub=force_hub, tile_rows=tile_rows)
                with obs_trace.maybe_span("session.readback"):
                    # count and live entries: the Pipe's one read-back
                    count, live = (int(v) for v in np.asarray(tally))
                entries[mode].append(live)
                # a sparse step's slots follow its live entries (csr-segment)
                slots[mode].append(
                    d_slots if use_dense
                    else alg.sparse_slots(ig, capacity, live, force_hub))
            trace.append(mode)
            if meter is not None:
                meter.add(t.seconds)
            if collect_tti:
                tti.append(t.seconds)
            if isinstance(pol, AutoTuned):
                pol.observe(use_dense, counts[-1], n, t.seconds)
            it += 1

        total = time.perf_counter() - t_start
        with obs_trace.maybe_span("session.finalize"):
            final, n_colors = alg.finalize(np.asarray(colors[:n]))
        return ColoringResult(colors=final, n_colors=n_colors, iterations=it,
                              mode_trace="".join(trace), counts=counts,
                              tti=tti, total_seconds=total,
                              host_dispatches=it,
                              sparse_entries=entries["S"],
                              sparse_slots=slots["S"],
                              dense_entries=entries["D"],
                              dense_slots=slots["D"])

    # -- device-resident outlined Pipe ---------------------------------------

    def _run_outlined(self, spec: ExecutionSpec, g, *, policy, collect_tti,
                      meter=None) -> ColoringResult:
        from repro.algos.ipgc_algo import IPGC
        alg = spec.resolved_algo()
        fused = alg.resolve_fused(spec.fused,
                                  default=jax.default_backend() == "tpu")
        with obs_trace.maybe_span("session.prepare") as sp:
            _, ig, window = self._prepare(spec, g, alg)
            if sp is not None:
                sp.attrs.update(_class_attrs(g, ig))
        n = ig.n_nodes
        pol = policy or make_policy(spec.mode, spec.h)
        caps = bucket_capacities(n, ratio=spec.bucket_ratio)
        lows = chunk_lower_bounds(caps)
        force_hub = False
        tile_rows = resolve_tile_rows(spec.tile_rows, ig.layout_kind,
                                      spec.impl)
        # None keeps the pre-subsystem IPGC jit specialisation
        # (bit-identical). Dataclass equality (not the name string) guards
        # the substitution: a subclass or re-registered variant under the
        # name "ipgc" compares unequal and traces its own step impls.
        algo_static = None if alg == IPGC() else alg
        if meter is not None:
            meter.statics = dict(kind="outlined", alg=alg, ig=ig,
                                 fused=fused, window=window, impl=spec.impl,
                                 force_hub=force_hub, tile_rows=tile_rows)

        colors, aux, wl = alg.init_state(ig)
        wl = resize_items(wl, caps[0], n)
        count = n

        trace: list[str] = []
        counts: list[int] = []
        tti: list[float] = []
        t_start = time.perf_counter()
        it = 0
        bi = 0
        dispatches = 0
        while count > 0 and it < spec.max_iter:
            while bi < len(caps) - 1 and caps[bi + 1] >= count:
                bi += 1
            wl = resize_items(wl, caps[bi], n)
            thresh = device_threshold(pol, n)
            # chunk counts stay in (lows[bi], caps[bi]]: compile out the
            # dense/sparse cond unless the H flip lands inside this chunk
            if lows[bi] >= thresh:
                branch = "dense"
            elif caps[bi] <= thresh:
                branch = "sparse"
            else:
                branch = "cond"
            counts.append(count)
            dispatches += 1
            with obs_trace.maybe_span("session.chunk", branch=branch,
                                      count=count, cap=caps[bi]), \
                    Timer() as t:
                colors, aux, wl, it_dev, nd, ns = _hybrid_chunk(
                    ig, colors, aux, wl,
                    jnp.asarray(thresh, jnp.int32),
                    jnp.asarray(lows[bi], jnp.int32),
                    jnp.asarray(spec.max_iter, jnp.int32),
                    jnp.asarray(it, jnp.int32),
                    jnp.asarray(0, jnp.int32),
                    jnp.asarray(0, jnp.int32),
                    algo=algo_static, window=window, impl=spec.impl,
                    fused=fused, force_hub=force_hub, branch=branch,
                    tile_rows=tile_rows)
                count = int(wl.count)  # the chunk's single scalar read-back
            nd, ns, new_it = int(nd), int(ns), int(it_dev)
            trace.append("D" * nd + "S" * ns)
            if meter is not None:
                meter.add(t.seconds)
            if collect_tti:
                tti.append(t.seconds)
            if isinstance(pol, AutoTuned):
                pol.observe_chunk(nd, ns, (counts[-1] + count) / 2,
                                  t.seconds)
            it = new_it

        total = time.perf_counter() - t_start
        final, n_colors = alg.finalize(np.asarray(colors[:n]))
        return ColoringResult(colors=final, n_colors=n_colors, iterations=it,
                              mode_trace="".join(trace), counts=counts,
                              tti=tti, total_seconds=total,
                              host_dispatches=dispatches)

    # -- sharded distributed Pipe --------------------------------------------

    def _run_dist(self, spec: ExecutionSpec, g, *, policy, collect_tti,
                  mesh, node_axes, meter=None) -> ColoringResult:
        from repro.core.distributed import make_dist_resize, views_to_colors
        from repro.graphs.partition import boundary_info, prepare_partition
        alg = spec.resolved_algo()
        if not alg.shard_safe:
            raise ValueError(
                f"algorithm {alg.name!r} is not shard-safe: "
                f"{alg.shard_unsafe_reason or 'no distributed steps'}")
        assert isinstance(g, Graph), "the dist regime needs a host Graph"
        plan = resolve_plan(g, spec.layout)
        if plan is not None and plan.kind == "csr-segment":
            raise NotImplementedError(
                "csr-segment execution has no shard_map steps (the "
                "edge-wise segment scatter is not owner-local); pass "
                "layout='ell-tail' to run this graph's ELL+tail arrays "
                "under the sharded Pipe")
        fused = alg.resolve_fused(spec.fused, default=True)
        custom_mesh = mesh is not None
        n_shards = spec.n_shards
        if mesh is None:
            if n_shards is None:
                n_shards = jax.device_count()
            mesh = jax.make_mesh((n_shards,), node_axes)
        else:
            n_shards = math.prod(mesh.shape[a] for a in node_axes)
        # auto-built meshes over the same device set are interchangeable;
        # a caller-provided mesh is cached by identity (steps close over
        # it). The algorithm and plan join as frozen instances. Unlike
        # the prep entries, the graph joins by CONTENT (name, sizes and a
        # checksum of its CSR): a caller that rebuilds an equal Graph per
        # request must still reuse the partitioned graph and jitted
        # shard_map steps, and a relabeled graph of the same name and
        # sizes must not.
        key = ("dist", g.name, g.n_nodes, g.n_edges, _csr_checksum(g),
               n_shards, node_axes,
               spec.window, spec.priority, fused, spec.balance, alg, plan,
               spec.tile_rows, spec.exchange,
               id(mesh) if custom_mesh else None)

        def build():
            g2, new_of_old = prepare_partition(g, n_shards,
                                               balance=spec.balance)
            if spec.window == "auto":
                window = adaptive_window(g2) if alg.uses_window else 128
            else:
                window = spec.window
            ig = alg.prepare(g2, priority=spec.priority, plan=plan)
            self._note_class(ig)
            binfo = thresh = None
            if spec.exchange != "dense":
                binfo = boundary_info(g2, n_shards)
                thresh = exchange_threshold(ig.n_nodes, n_shards,
                                            spec.exchange)
            dense_fn, sparse_fn = alg.make_dist_steps(
                ig, mesh, node_axes, window=window, fused=fused,
                exchange=spec.exchange, boundary=binfo, thresh=thresh)
            resize_fn = make_dist_resize(mesh, node_axes, ig.n_nodes)
            return (g, g2, new_of_old, ig, window, dense_fn, sparse_fn,
                    resize_fn, binfo, thresh)

        with obs_trace.maybe_span("session.prepare") as sp:
            (_, g2, new_of_old, ig, window, dense_fn, sparse_fn,
             resize_fn, binfo, thresh) = self.cached(key, build)
            if sp is not None:
                sp.attrs.update(_class_attrs(g2, ig))
        n = ig.n_nodes
        if meter is not None:
            meter.statics = dict(kind="dist", alg=alg, ig=ig, mesh=mesh,
                                 node_axes=node_axes, window=window,
                                 fused=fused, exchange=spec.exchange,
                                 binfo=binfo, thresh=thresh,
                                 n_shards=n_shards, dist_key=key)
        block = n // n_shards
        pol = policy or make_policy(spec.mode, spec.h)
        caps = bucket_capacities(block, ratio=spec.bucket_ratio)

        colors, base, wl = alg.init_state(ig)
        count = n
        bnd = spec.exchange != "dense"
        epi = getattr(dense_fn, "exchanges_per_iter", 1)
        xtrace: list[str] = []
        xbytes: list[int] = []
        if bnd:
            # per-shard color VIEWS (DESIGN.md §13): every view starts as
            # the replicated init vector, then tracks owned + ghost slots
            colors = jnp.broadcast_to(colors, (n_shards,) + colors.shape)
            bcaps = list(binfo.capacities)
            prev_mx = block   # changed-boundary high-water for prediction

        trace: list[str] = []
        counts: list[int] = []
        tti: list[float] = []
        t_start = time.perf_counter()
        it = 0
        while count > 0 and it < spec.max_iter:
            use_dense = bool(pol(count, n))
            counts.append(count)
            with obs_trace.maybe_span(
                    "session.iter", mode="D" if use_dense else "S",
                    count=count), Timer() as t:
                if use_dense:
                    if bnd:
                        bcap = pick_bucket(
                            bcaps, min(block, max(8, 2 * prev_mx)))
                        colors, base, wl, xs = dense_fn(colors, base, wl,
                                                        bcap=bcap)
                    else:
                        colors, base, wl = dense_fn(colors, base, wl)
                else:
                    # any shard's live count is <= min(global count, block)
                    cap = pick_bucket(caps, min(count, block))
                    if wl.items.shape[0] > n_shards * cap:
                        wl = resize_fn(wl, cap)
                    if bnd:
                        # changed boundary slots are also <= the worklist
                        # capacity a sparse iteration runs at
                        bcap = pick_bucket(
                            bcaps, min(cap, block, max(8, 2 * prev_mx)))
                        colors, base, wl, xs = sparse_fn(colors, base, wl,
                                                         bcap=bcap)
                    else:
                        colors, base, wl = sparse_fn(colors, base, wl)
                count = int(wl.count)  # the Pipe's single scalar read-back
                if bnd:
                    # one device->host transfer for both stats
                    npk, prev_mx = (int(v) for v in np.asarray(xs))
                    xtrace.append("b" if npk == epi
                                  else ("d" if npk == 0 else "m"))
                    xbytes.append(
                        npk * packed_exchange_bytes(bcap, n_shards)
                        + (epi - npk) * dense_swap_bytes(n))
            trace.append("D" if use_dense else "S")
            if meter is not None:
                meter.add(t.seconds)
            if collect_tti:
                tti.append(t.seconds)
            if isinstance(pol, AutoTuned):
                pol.observe(use_dense, counts[-1], n, t.seconds)
            it += 1

        total = time.perf_counter() - t_start
        if bnd:
            full = views_to_colors(np.asarray(colors), n_shards, n)
        else:
            full = np.asarray(colors[:n])
            xtrace = ["d"] * it
            xbytes = [epi * dense_exchange_bytes(n)] * it
        final = full[new_of_old[:g.n_nodes]]   # back to original labels
        final, n_colors = alg.finalize(final)
        return ColoringResult(colors=final, n_colors=n_colors, iterations=it,
                              mode_trace="".join(trace), counts=counts,
                              tti=tti, total_seconds=total,
                              host_dispatches=it,
                              exchange_trace="".join(xtrace),
                              exchange_bytes=xbytes)


# ---------------------------------------------------------------------------
# the outlined chunk program
# ---------------------------------------------------------------------------

def _chunk_impl(ig, colors, aux, wl, thresh, low, max_iter, it0, nd0, ns0,
                *, algo=None, window: int, impl: str, fused: bool,
                force_hub: bool, branch: str,
                tile_rows: "int | None" = None):
    """One device program: while_loop over hybrid iterations at a static
    capacity bucket. Each trip picks dense vs sparse via ``lax.cond`` on
    the on-device count; the loop exits when the count crosses ``low``
    (the next bucket boundary) so the host can re-dispatch at a smaller
    static shape.

    ``algo`` is a static (hashable) Algorithm whose step impls trace into
    the loop body; ``None`` resolves to IPGC — the pre-subsystem jaxpr.

    ``branch`` is a host-side specialisation: when the whole chunk
    provably runs one mode (its count range ``(low, cap]`` sits entirely
    on one side of the threshold — true for every chunk except the one
    containing the H flip), the conditional is compiled out so XLA sees a
    straight-line loop body.
    """
    if algo is None:
        dense_fn = (ipgc.fused_dense_step_impl if fused
                    else ipgc.dense_step_impl)
        sparse_fn = (ipgc.fused_sparse_step_impl if fused
                     else ipgc.sparse_step_impl)
    else:
        dense_fn, sparse_fn = algo.step_impls(fused)
    step_kw = dict(window=window, impl=impl, force_hub=force_hub,
                   tile_rows=tile_rows)

    def cond(state):
        _, _, wl, it, _, _ = state
        return (wl.count > 0) & (it < max_iter) & (wl.count > low)

    def body(state):
        colors, aux, wl, it, nd, ns = state
        if branch == "dense":
            use_dense = jnp.asarray(True)
            colors, aux, wl = dense_fn(ig, colors, aux, wl, **step_kw)
        elif branch == "sparse":
            use_dense = jnp.asarray(False)
            colors, aux, wl = sparse_fn(ig, colors, aux, wl, **step_kw)
        else:
            use_dense = wl.count > thresh
            colors, aux, wl = jax.lax.cond(
                use_dense,
                lambda c, b, w: dense_fn(ig, c, b, w, **step_kw),
                lambda c, b, w: sparse_fn(ig, c, b, w, **step_kw),
                colors, aux, wl)
        d = use_dense.astype(jnp.int32)
        return colors, aux, wl, it + 1, nd + d, ns + (1 - d)

    return jax.lax.while_loop(
        cond, body, (colors, aux, wl, it0, nd0, ns0))


_hybrid_chunk = jax.jit(
    _chunk_impl,
    static_argnames=("algo", "window", "impl", "fused", "force_hub",
                     "branch", "tile_rows"))


# ---------------------------------------------------------------------------
# process-default session
# ---------------------------------------------------------------------------

_DEFAULT_SESSION: Session | None = None


def default_session() -> Session:
    """The process-wide session, so callers that do not keep their own
    amortize preparation across requests."""
    global _DEFAULT_SESSION
    if _DEFAULT_SESSION is None:
        # bounded: entries pin graphs, and nothing ever clears the
        # process-default store — an endless stream of distinct graphs
        # must not grow memory without limit
        _DEFAULT_SESSION = Session(max_entries=256)
    return _DEFAULT_SESSION


def reset_default_session() -> None:
    """Drop the process-default session (tests; frees pinned graphs)."""
    global _DEFAULT_SESSION
    _DEFAULT_SESSION = None

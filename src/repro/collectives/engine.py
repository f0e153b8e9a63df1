"""CollectiveEngine: one dispatch layer for all collective traffic.

The paper's contribution is *model-driven selection*: evaluate every
implemented algorithm under the spatial performance model (Eq. 1) and
run the winner.  The engine makes that a subsystem instead of a per-call
computation:

* **Ops** -- ``allreduce``, ``reduce``, ``reduce_scatter``,
  ``allgather``, ``broadcast``, each with fixed-pattern backends and an
  Auto-Gen (DP tree) backend, selected by ``algorithm="auto"``.
* **Decision cache** -- selections are memoized by
  ``(op, P, bytes, fabric)`` and persisted as JSON under the same
  ``REPRO_CACHE_DIR`` the Auto-Gen npz tables use, so the DP and the
  model sweep run once per shape across traces *and* processes.
* **Tree cache** -- extracted Auto-Gen round schedules are memoized by
  ``(P, elements)`` so an explicit ``algorithm="autogen"`` trace never
  re-runs the DP either.
* **Calibration** -- ``calibrate()`` refits the Fabric constants from
  measured ppermute timings (``measure_ppermute``), so selection tracks
  the actual backend instead of the baked-in ICI constants.  With a
  mesh (or per-axis measurement dicts) it fits one Fabric *per mesh
  axis* on a shared time base, producing a heterogeneous
  ``FabricTopology`` -- pod links slower than intra-pod ICI -- that the
  planner prices per axis and the v3 cache persists.

Dispatch flow::

    user op (allreduce/reduce_scatter/...)          [api.py wrappers]
        -> engine.<op>_inside(x, axis, algorithm)   [inside shard_map]
            -> select(op, nbytes, P)                [decision cache]
                -> selector.predict_collective      [model, Eq. 1]
                -> autogen DP (tree cache, npz)     [only if needed]
            -> shardmap_impl backend                [rounds of ppermutes]
"""

from __future__ import annotations

import atexit
import dataclasses
import json
import os
import threading
import time
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P
from jax import shard_map

from repro.core.autogen import autogen_tree, cache_dir, compute_tables
from repro.core.model import (Fabric, FabricTopology, TPU_V5E_AXIS,
                              as_topology, ceil_div)
from repro.core import patterns as pat
from repro.core import selector
from repro.obs import trace as obs_trace
from repro.collectives import planner
from repro.collectives import shardmap_impl as impl

#: one model "element" on the TPU fabric (512-byte flit group)
ICI_ELEMENT_BYTES = 512

#: bump when the cost model changes (patterns/selector/planner) so
#: persisted decisions computed under the old model stop being served.
#: v2: chunk-pipelined plan candidates + overlap-aware lower bounds.
#: v3: per-launch overhead terms (Fabric.t_launch) + the one-shot
#: latency-regime candidates ("oneshot" algorithm, "latency" plan shape).
MODEL_VERSION = 3

#: persisted-file layout version.  v2 keys decisions by the full
#: topology signature (``op|t=2x8|B=...``) instead of the bare axis size
#: (``op|p=16|B=...``) and adds the ``plans`` section; v3 namespaces the
#: file by the full *fabric topology* (per-axis constants in the tag,
#: ``|f=`` key suffixes for non-default axis fabrics) and persists the
#: topology itself in a ``topology`` section so per-axis calibrations
#: survive the process.  v1/v2 files are migrated on load (v1 keys are
#: 1D signatures by construction; v2 keys are already topology
#: signatures, and a uniform topology's tag equals the v2 tag).
SCHEMA_VERSION = 3

Rounds = Tuple[Tuple[Tuple[int, int], ...], ...]


def _topo_key(op: str, topo: Sequence[int], nbytes: int) -> str:
    return f"{op}|t={'x'.join(str(int(s)) for s in topo)}|B={nbytes}"


def _freeze_rounds(rounds: Sequence[Sequence[Tuple[int, int]]]) -> Rounds:
    return tuple(tuple((int(s), int(d)) for s, d in r) for r in rounds)


@dataclasses.dataclass(frozen=True)
class Decision:
    """One cached selection: what to run for (op, P, bytes)."""

    op: str
    p: int
    nbytes: int
    algorithm: str
    predicted: float
    predictions: Dict[str, float]
    rounds: Optional[Rounds] = None   # Auto-Gen schedule, when selected


def _fit_line(measurements: Sequence[Tuple[int, float]],
              element_bytes: int) -> Tuple[float, float]:
    """Least-squares ``seconds = alpha + beta * B`` over one axis's
    neighbor-ppermute timings; returns the raw ``(alpha, beta)`` --
    callers decide how to treat a degenerate (non-positive) slope."""
    if len(measurements) < 2:
        raise ValueError("need >= 2 (nbytes, seconds) points to calibrate")
    els = np.array([max(1, nb // element_bytes) for nb, _ in measurements],
                   dtype=np.float64)
    secs = np.array([t for _, t in measurements], dtype=np.float64)
    beta, alpha = np.polyfit(els, secs, 1)
    return float(alpha), float(beta)


def fit_fabric(measurements: Sequence[Tuple[int, float]],
               base: Fabric = TPU_V5E_AXIS, name: Optional[str] = None,
               element_bytes: int = ICI_ELEMENT_BYTES,
               ref_cycle: Optional[float] = None) -> Fabric:
    """Fit Fabric constants from measured one-hop ppermute timings.

    ``measurements`` is a sequence of ``(nbytes, seconds)`` for a single
    neighbor ppermute.  Under the model a hop costs
    ``(2*t_r + B / link_bw) * cycle`` seconds with B in elements, so a
    least-squares line ``seconds = alpha + beta * B`` recovers the
    constants.  With ``ref_cycle=None`` the axis defines its own time
    base (``cycle = beta``, ``t_r = alpha / (2 * beta)``,
    ``link_bw = base.link_bw``) -- only the ratios enter 1D selection.
    Fitting several axes of one mesh needs a *shared* time base so their
    prices are comparable inside one plan: pass the fastest axis's beta
    as ``ref_cycle`` and the fit recovers ``link_bw = ref_cycle / beta``
    (< 1 for slower links) and ``t_r = alpha / (2 * ref_cycle)``.
    """
    alpha, beta = _fit_line(measurements, element_bytes)
    beta = max(beta, 1e-30)
    if ref_cycle is None:
        # the fitted slope is cycle / link_bw; keeping base.link_bw
        # means the implied cycle is beta * link_bw, and t_r must be
        # expressed in those cycles
        t_r = max(alpha / (2.0 * beta * base.link_bw), 0.0)
        link_bw = base.link_bw
    else:
        ref = max(float(ref_cycle), 1e-30)
        t_r = max(alpha / (2.0 * ref), 0.0)
        link_bw = ref / beta
    return Fabric(name=name or f"{base.name}_calibrated",
                  t_r=t_r, store_cost=base.store_cost,
                  link_bw=link_bw, multicast=base.multicast)


def measure_ppermute(mesh: Mesh, axis: str,
                     sizes_bytes: Sequence[int] = (1 << 12, 1 << 16,
                                                   1 << 20, 1 << 22),
                     repeats: int = 5) -> List[Tuple[int, float]]:
    """Time one neighbor-shift ppermute per size; feed to ``fit_fabric``."""
    p = mesh.shape[axis]
    perm = [(i, (i + 1) % p) for i in range(p)]

    out = []
    for nbytes in sizes_bytes:
        n = max(1, nbytes // 4)
        x = jnp.zeros((n,), jnp.float32)

        fn = shard_map(lambda v: lax.ppermute(v, axis, perm), mesh=mesh,
                       in_specs=P(), out_specs=P(), check_vma=False)
        jitted = jax.jit(fn)
        jitted(x).block_until_ready()          # compile
        best = float("inf")
        for _ in range(repeats):
            t0 = time.perf_counter()
            jitted(x).block_until_ready()
            best = min(best, time.perf_counter() - t0)
        out.append((nbytes, best))
    return out


def fabric_to_dict(f: Fabric) -> Dict[str, Any]:
    return {"name": f.name, "t_r": f.t_r, "store_cost": f.store_cost,
            "link_bw": f.link_bw, "multicast": f.multicast,
            "t_launch": f.t_launch}


def _fabric_from_dict(d: Dict[str, Any]) -> Fabric:
    return Fabric(name=str(d["name"]), t_r=float(d["t_r"]),
                  store_cost=float(d["store_cost"]),
                  link_bw=float(d.get("link_bw", 1.0)),
                  multicast=bool(d.get("multicast", True)),
                  t_launch=float(d.get("t_launch", 0.0)))


def topology_to_dict(t: FabricTopology) -> Dict[str, Any]:
    return {"name": t.name, "default": fabric_to_dict(t.default),
            "axes": {axis: fabric_to_dict(f)
                     for axis, f in t.axis_fabrics}}


def topology_from_dict(d: Dict[str, Any]) -> FabricTopology:
    return FabricTopology(
        default=_fabric_from_dict(d["default"]),
        axis_fabrics=tuple((axis, _fabric_from_dict(fd))
                           for axis, fd in d.get("axes", {}).items()),
        name=str(d.get("name", "")))


def load_topology(path: str) -> Optional[FabricTopology]:
    """Read the fabric topology a v3 cache file was computed under
    (None for v1/v2 files or unreadable paths) -- how a fresh process
    restores a prior per-axis calibration."""
    try:
        with open(path) as f:
            payload = json.load(f)
    except (OSError, ValueError):
        return None
    topo = payload.get("topology")
    if not topo:
        return None
    try:
        return topology_from_dict(topo)
    except (KeyError, TypeError, ValueError):
        return None


def find_calibrated_topology(base: Fabric = TPU_V5E_AXIS
                             ) -> Optional[FabricTopology]:
    """Newest fleet-calibrated :class:`FabricTopology` persisted under
    ``REPRO_CACHE_DIR`` (the v3 ``topology`` section), or None.

    Only *calibrated* topologies qualify (``calibrate()`` names them
    ``<base>_calibrated``): a topology merely declared via a
    ``--fabric`` spec describes one launch's assumption, not a measured
    fleet property, and must not leak into unrelated processes sharing
    the cache directory.  Likewise only ``base``'s constants family is
    considered (the calibration keeps ``base`` as the default fabric),
    so a WSE cache never leaks into a TPU engine.  Set
    ``REPRO_RESTORE_TOPOLOGY=0`` to opt out -- e.g. when a process must
    price with the stock constants regardless of what a previous
    calibration run left behind."""
    if os.environ.get("REPRO_RESTORE_TOPOLOGY", "1").lower() in (
            "0", "false", "no", ""):
        return None
    d = cache_dir()
    try:
        names = os.listdir(d)
    except OSError:
        return None
    paths = [os.path.join(d, n) for n in names
             if n.startswith("engine_decisions__") and n.endswith(".json")]

    def _mtime(p: str) -> float:
        try:
            return os.path.getmtime(p)
        except OSError:
            return 0.0

    for path in sorted(paths, key=_mtime, reverse=True):
        topo = load_topology(path)
        if topo is None:
            continue
        if not topo.name.endswith("_calibrated"):
            continue        # declared (--fabric) rather than measured
        if topo.is_uniform and topo.default == base:
            continue        # nothing beyond the stock constants
        if (topo.default != base
                and not topo.default.name.startswith(base.name)):
            continue        # a different fabric family's cache
        return topo
    return None


class CollectiveEngine:
    """Cached, model-driven dispatch for every collective op.

    One engine per fabric-topology parameterization; ``api.get_engine()``
    hands out a process-wide default keyed by fabric so all call sites
    share one decision cache.  ``fabric`` may be a bare :class:`Fabric`
    (every axis priced the same -- the uniform fast path) or a
    :class:`FabricTopology` mapping mesh axis names to per-axis
    constants, in which case the planner prices each phase with the
    constants of the axes it actually traverses.
    """

    def __init__(self, fabric: "Fabric | FabricTopology" = TPU_V5E_AXIS,
                 cache_path: Optional[str] = None, persist: bool = True,
                 element_bytes: int = ICI_ELEMENT_BYTES):
        self.topology = as_topology(fabric)
        self.element_bytes = element_bytes
        self._persist = persist
        self._cache_path_override = cache_path
        self._decisions: Dict[str, Decision] = {}
        self._plans: Dict[str, Dict[str, Any]] = {}
        self._tree_rounds: Dict[Tuple[int, int], Rounds] = {}
        self._tables: Dict[int, Any] = {}
        self._loaded = False
        self._lock = threading.RLock()
        self._dirty = False
        self._last_save = 0.0
        self.stats = {"hits": 0, "misses": 0, "dp_runs": 0,
                      "persisted_loads": 0, "plan_hits": 0,
                      "plan_misses": 0, "latency_dispatches": 0}
        if persist:
            atexit.register(self.flush)

    @property
    def fabric(self) -> Fabric:
        """The topology's default fabric (the pre-topology engines'
        single Fabric; per-axis overrides live in ``self.topology``)."""
        return self.topology.default

    # ------------------------------------------------------------------ #
    # decision cache
    # ------------------------------------------------------------------ #
    @staticmethod
    def _fabric_one_tag(f: Fabric) -> str:
        tag = (f"{f.name}_tr{f.t_r:g}_st{f.store_cost:g}"
               f"_bw{f.link_bw:g}_mc{int(f.multicast)}")
        # uncalibrated fabrics keep the exact pre-t_launch tag, so
        # existing cache files stay valid until a launch calibration
        # actually moves the constants
        if f.t_launch != 0.0:
            tag += f"_tl{f.t_launch:g}"
        return tag

    def _fabric_tag(self) -> str:
        """Cache namespace: the full topology signature.  A uniform
        topology produces exactly the v2 single-fabric tag, so uniform
        engines keep their existing cache files; per-axis overrides
        append to the tag (fresh namespace per calibration)."""
        tag = (f"{self._fabric_one_tag(self.topology.default)}"
               f"_eb{self.element_bytes}_v{MODEL_VERSION}")
        for axis, f in self.topology.axis_fabrics:
            tag += f"__{axis}-{self._fabric_one_tag(f)}"
        return tag

    def _cache_path(self) -> str:
        if self._cache_path_override:
            return self._cache_path_override
        return os.path.join(cache_dir(),
                            f"engine_decisions__{self._fabric_tag()}.json")

    def _elements(self, nbytes: int) -> int:
        return max(1, nbytes // self.element_bytes)

    def _load_persisted(self) -> None:
        if self._loaded or not self._persist:
            self._loaded = True
            return
        self._loaded = True
        path = self._cache_path()
        if not os.path.exists(path):
            return
        try:
            with open(path) as f:
                payload = json.load(f)
        except (OSError, ValueError):
            return
        # decisions are only valid for the constants they were computed
        # under (matters when cache_path pins the file name but
        # calibrate() swaps the fabric); a uniform topology's tag equals
        # the v2 single-fabric tag, so v2 files migrate transparently
        if payload.get("fabric") != self._fabric_tag():
            return
        schema = int(payload.get("schema", 1))
        if schema > SCHEMA_VERSION:
            return     # written by a newer build; recompute instead
        for key, d in payload.get("decisions", {}).items():
            if schema < 2:
                # v1 keys are "op|p=8|B=..."; every v1 entry is a bare
                # 1D axis, so its topology signature is just (p,)
                key = key.replace("|p=", "|t=", 1)
            rounds = (_freeze_rounds(d["rounds"])
                      if d.get("rounds") else None)
            self._decisions[key] = Decision(
                op=d["op"], p=int(d["p"]), nbytes=int(d["nbytes"]),
                algorithm=d["algorithm"], predicted=float(d["predicted"]),
                predictions={k: float(v)
                             for k, v in d["predictions"].items()},
                rounds=rounds)
            self.stats["persisted_loads"] += 1
        for key, rec in payload.get("plans", {}).items():
            self._plans[key] = rec
            self.stats["persisted_loads"] += 1

    def _maybe_save(self) -> None:
        """Write-behind: cold-start sweeps decide many shapes back to
        back, so full-file rewrites are throttled to ~1/s; ``flush()``
        (also registered atexit) writes the tail."""
        if self._dirty and time.monotonic() - self._last_save >= 1.0:
            self._save_persisted()

    def flush(self) -> None:
        """Force any unsaved decisions to disk now."""
        with self._lock:
            if self._dirty:
                self._save_persisted()

    def _save_persisted(self) -> None:
        if not self._persist:
            self._dirty = False
            return
        raw = {}
        for key, d in self._decisions.items():
            raw[key] = {"op": d.op, "p": d.p, "nbytes": d.nbytes,
                        "algorithm": d.algorithm, "predicted": d.predicted,
                        "predictions": d.predictions,
                        "rounds": [[list(s) for s in r] for r in d.rounds]
                        if d.rounds else None}
        try:
            path = self._cache_path()
            os.makedirs(os.path.dirname(path), exist_ok=True)
            tmp = path + f".tmp{os.getpid()}"
            with open(tmp, "w") as f:
                json.dump({"schema": SCHEMA_VERSION,
                           "fabric": self._fabric_tag(),
                           "topology": topology_to_dict(self.topology),
                           "decisions": raw, "plans": self._plans}, f)
            os.replace(tmp, path)
        except OSError:
            # unwritable/bogus cache dir: selection still works, it just
            # stays in-memory for this process
            self._persist = False
        self._dirty = False
        self._last_save = time.monotonic()

    def _tables_for(self, p: int):
        tables = self._tables.get(p)
        if tables is None:
            tables = compute_tables(p)
            self._tables[p] = tables
        return tables

    def tree_rounds(self, p: int, b_elements: int,
                    fabric: Optional[Fabric] = None) -> Rounds:
        """Auto-Gen round schedule for (P, B, fabric), DP'd at most
        once; ``fabric`` defaults to the topology's default fabric."""
        fab = fabric or self.topology.default
        with self._lock:
            key = (p, b_elements, fab)
            rounds = self._tree_rounds.get(key)
            if rounds is None:
                self.stats["dp_runs"] += 1
                tree = autogen_tree(p, b_elements, fabric=fab,
                                    tables=self._tables_for(p))
                rounds = _freeze_rounds(tree.to_rounds())
                self._tree_rounds[key] = rounds
            return rounds

    def _fabric_key_suffix(self, fabric: Optional[Fabric]) -> str:
        """Per-axis constants enter the cache key only when they differ
        from the default fabric, so uniform topologies keep the exact v2
        key space."""
        if fabric is None or fabric == self.topology.default:
            return ""
        return f"|f={self._fabric_one_tag(fabric)}"

    def stats_snapshot(self) -> Dict[str, int]:
        """Atomic copy of the cache counters.  ``self.stats`` is
        mutated in place by every caller sharing the engine; exports
        (and asserting tests) want one consistent view, which this
        returns under the same lock the mutations hold."""
        with self._lock:
            return dict(self.stats)

    def select(self, op: str, nbytes: int, p: int,
               topo: Optional[Tuple[int, ...]] = None,
               fabric: Optional[Fabric] = None) -> Decision:
        d, _ = self._select_meta(op, nbytes, p, topo=topo, fabric=fabric)
        return d

    def _select_meta(self, op: str, nbytes: int, p: int,
                     topo: Optional[Tuple[int, ...]] = None,
                     fabric: Optional[Fabric] = None
                     ) -> Tuple[Decision, bool]:
        """Model-driven selection, memoized by the full topology
        signature ``(op, axis_sizes, bytes, fabric)``.  Returns the
        decision plus whether it came from the cache (the bit a span
        records).

        For a bare 1D axis the signature is ``(p,)``; a folded logical
        axis passes its shape as ``topo`` (e.g. ``(2, 8)``) so a 16-way
        ``data`` axis and a 16-way folded ``(pod, data)`` topology never
        share cache entries even though their modeled costs coincide on
        a uniform fabric -- per-axis calibration splits them.
        ``fabric`` prices the candidate set with axis-local constants (a
        non-default axis of a heterogeneous topology); such decisions
        are keyed with an ``|f=`` suffix so the same axis size under
        different link constants never collides.

        ``allreduce`` keeps the paper-selector candidate set (fixed
        patterns + ring); the other ops additionally model their
        Auto-Gen backend, so a cache miss may run the DP (counted in
        ``stats['dp_runs']`` via the tree/table caches).
        """
        if p <= 1:
            return Decision(op, p, nbytes, "identity", 0.0, {}), False
        fab = fabric or self.topology.default
        with self._lock:
            self._load_persisted()
            key = (_topo_key(op, topo or (p,), nbytes)
                   + self._fabric_key_suffix(fabric))
            hit = self._decisions.get(key)
            if hit is not None:
                self.stats["hits"] += 1
                return hit, True
            self.stats["misses"] += 1
            b = self._elements(nbytes)
            # allreduce keeps the paper-selector candidate set; all_to_all
            # has no reduction tree, so neither models an Auto-Gen backend
            include_autogen = op not in ("allreduce", "all_to_all")
            if include_autogen:
                tables = self._tables_for(p)
            else:
                tables = None
            preds = selector.predict_collective(
                op, p, b, fab, include_autogen=include_autogen,
                tables=tables)
            if op == "allreduce":
                # the paper's TPU selector: star loses to its own
                # broadcast on ICI, so it is not a candidate
                preds.pop("star", None)
            name = min(preds, key=preds.get)
            rounds = (self.tree_rounds(p, self._tree_elements(op, b, p),
                                       fabric=fab)
                      if name == "autogen" else None)
            decision = Decision(op, p, nbytes, name, preds[name],
                                {k: float(v) for k, v in preds.items()},
                                rounds)
            self._decisions[key] = decision
            self._dirty = True
            self._maybe_save()
            return decision, False

    def plan_multi(self, op: str, axes: Sequence[str],
                   sizes: Sequence[int], nbytes: int,
                   shape: Optional[str] = None) -> planner.CollectivePlan:
        """Public cover of :meth:`_plan_multi_meta`: the plan without
        the cache-hit bit.  Annotates the innermost open span (if any)
        with the chosen plan and its predicted cost."""
        plan, hit = self._plan_multi_meta(op, axes, sizes, nbytes,
                                          shape=shape)
        sp = obs_trace.get_tracer().current_span()
        if getattr(sp, "args", {}).get("plan") is None:
            sp.set(plan=plan.describe(), n_chunks=int(plan.n_chunks),
                   algorithm=str(plan.shape),
                   predicted=float(plan.predicted),
                   cache="hit" if hit else "miss")
            if shape is not None:
                sp.set(algorithm_forced=True)
        return plan

    def _plan_multi_meta(self, op: str, axes: Sequence[str],
                         sizes: Sequence[int], nbytes: int,
                         shape: Optional[str] = None
                         ) -> Tuple[planner.CollectivePlan, bool]:
        """Topology-aware joint plan for an axis tuple, memoized and
        persisted by ``(op, axis_sizes, bytes, fabric)``.  Returns the
        bound plan plus whether the scored record came from the cache.

        Each axis is priced with its fabric from ``self.topology`` (by
        axis *name*), so hierarchical compositions genuinely win when
        pod links are slower than intra-pod ICI.  Plans whose axes use
        non-default fabrics carry those constants in the cache key --
        the same ``(2, 8)`` shape under different axis bindings never
        collides; uniform topologies keep the exact v2 key space and
        rebind freely across mesh axis names.

        ``shape`` forces a candidate ("hierarchical", "2d_xy", ...)
        instead of taking the model argmin; forced plans are derived
        from the same scored record, so they are cached once too.
        """
        axes = tuple(axes)
        sizes = tuple(int(s) for s in sizes)
        if len(axes) != len(sizes):
            raise ValueError(f"axes {axes} vs sizes {sizes}")
        axis_fabrics = tuple(self.topology.for_axis(a) for a in axes)
        with self._lock:
            self._load_persisted()
            key = _topo_key(op, sizes, nbytes)
            if any(f != self.topology.default for f in axis_fabrics):
                key += "|f=" + ",".join(self._fabric_one_tag(f)
                                        for f in axis_fabrics)
            if shape is not None:
                key += f"|shape={shape}"
            rec = self._plans.get(key)
            hit = rec is not None
            if rec is None:
                self.stats["plan_misses"] += 1
                rec = planner.plan_collective(
                    op, sizes, nbytes, self.fabric, self.element_bytes,
                    self.select, force_shape=shape,
                    axis_fabrics=axis_fabrics)
                self._plans[key] = rec
                self._dirty = True
                self._maybe_save()
            else:
                self.stats["plan_hits"] += 1
        return planner.bind_plan(rec, op, axes), hit

    def clear_cache(self) -> None:
        with self._lock:
            self._decisions.clear()
            self._plans.clear()
            self._tree_rounds.clear()
            self._tables.clear()
            self._loaded = False

    def decision_table(self) -> List[Decision]:
        """Everything decided so far (introspection/reporting)."""
        with self._lock:
            self._load_persisted()
            return sorted(self._decisions.values(),
                          key=lambda d: (d.op, d.p, d.nbytes))

    # ------------------------------------------------------------------ #
    # calibration
    # ------------------------------------------------------------------ #
    def calibrate(self,
                  measurements: Optional[Any] = None,
                  mesh: Optional[Mesh] = None, axis: Optional[str] = None,
                  sizes_bytes: Sequence[int] = (1 << 12, 1 << 16, 1 << 20,
                                                1 << 22)
                  ) -> "Fabric | FabricTopology":
        """Refit the fabric constants from timings and drop stale
        decisions.

        * ``measurements=[(nbytes, seconds), ...]`` -- refit the default
          fabric (uniform topology); returns the fitted :class:`Fabric`.
        * ``measurements={axis: [(nbytes, seconds), ...], ...}`` -- fit
          one Fabric *per axis* on a shared time base: the fastest
          axis's fitted cycle anchors ``link_bw=1`` and slower axes get
          proportionally smaller ``link_bw`` (and their own ``t_r``).
          Returns the new :class:`FabricTopology`.
        * ``mesh=...`` -- run ``measure_ppermute`` per mesh axis (every
          axis of size > 1, or just ``axis`` if given) and fit per-axis
          as above.

        Either way the engine's cache namespace moves to the new
        constants; the next persisted save records the topology in the
        v3 ``topology`` section.
        """
        if measurements is None:
            if mesh is None:
                raise ValueError("calibrate() needs measurements or a mesh")
            axes = ([axis] if axis is not None
                    else [a for a in mesh.axis_names if mesh.shape[a] > 1])
            if not axes:
                raise ValueError(
                    f"calibrate(mesh=...): no axis of size > 1 to "
                    f"measure in mesh {dict(mesh.shape)}")
            measurements = {a: measure_ppermute(mesh, a, sizes_bytes)
                            for a in axes}
        with self._lock:
            base = self.topology.default
            if isinstance(measurements, dict):
                if not measurements:
                    raise ValueError("calibrate() got an empty per-axis "
                                     "measurements dict")
                lines = {a: _fit_line(m, self.element_bytes)
                         for a, m in measurements.items()}
                # a non-positive -- or vanishing -- fitted slope means
                # the timings carry no bandwidth signal; anchoring the
                # shared time base on it would poison every axis's
                # constants (link_bw ratios of ~1e-20) -- fail loudly
                bad = []
                for a, m in measurements.items():
                    alpha, beta = lines[a]
                    els = [max(1, nb // self.element_bytes)
                           for nb, _ in m]
                    rise = beta * (max(els) - min(els))
                    scale = abs(alpha) + abs(beta) * max(els) + 1e-30
                    if beta <= 0.0 or rise < 1e-6 * scale:
                        bad.append(a)
                bad.sort()
                if bad:
                    raise ValueError(
                        f"calibrate(): non-positive fitted slope for "
                        f"axis(es) {bad}; timings are noise-dominated "
                        f"-- raise sizes_bytes/repeats or calibrate "
                        f"those axes separately")
                # shared time base: the fastest axis's seconds/element
                ref = min(beta for _, beta in lines.values())
                fitted = tuple(
                    (a, fit_fabric(measurements[a], base=base,
                                   name=f"{base.name}_{a}",
                                   element_bytes=self.element_bytes,
                                   ref_cycle=ref))
                    for a in sorted(measurements))
                result: "Fabric | FabricTopology" = FabricTopology(
                    default=base, axis_fabrics=fitted,
                    name=f"{base.name}_calibrated")
                self.topology = result
            else:
                fitted_f = fit_fabric(measurements, base=base,
                                      element_bytes=self.element_bytes)
                self.topology = FabricTopology.uniform(fitted_f)
                result = fitted_f
            # fabric changed => cache namespace (file name) changed too;
            # in-memory decisions and plans predate the new constants
            self._decisions.clear()
            self._plans.clear()
            self._tree_rounds.clear()
            self._loaded = False
        return result

    def calibrate_launch(self,
                         samples: Sequence[Tuple[str, int, int, str, float]]
                         ) -> float:
        """Fit ``Fabric.t_launch`` from measured collective wall times.

        ``samples`` is ``[(op, p, nbytes, algorithm, seconds), ...]`` --
        exactly what ``obs.replay.measure_spans`` produces for decode
        traces (``nbytes`` in the model's convention: global bytes for
        allgather).  Under the model a run costs
        ``seconds = cycle * (base_i + t_launch * L_i)`` where ``base_i``
        is the closed form at ``t_launch = 0`` and ``L_i`` the number of
        sequential program launches (:func:`patterns.launch_count`), so
        a two-column least squares over ``(base_i, L_i)`` recovers the
        seconds-per-cycle scale ``c`` and the per-launch seconds ``d``;
        ``t_launch = d / c`` converts back to model cycles.  Mixing
        sizes *and* algorithms with different launch counts is what
        makes the two columns separable -- an all-oneshot sample set
        cannot identify the constant.

        The engine's topology moves to the fitted constant (every
        per-axis fabric gets the same ``t_launch``: launch overhead is a
        host/framework property, not a per-link one), the cache
        namespace moves with it, and stale decisions are dropped.
        Returns the fitted ``t_launch`` (cycles, >= 0)."""
        samples = list(samples)
        if len(samples) < 2:
            raise ValueError("calibrate_launch() needs >= 2 samples")
        rows, secs = [], []
        base_fab = dataclasses.replace(self.topology.default,
                                       t_launch=0.0)
        for op, p, nbytes, algorithm, seconds in samples:
            b = self._elements(int(nbytes))
            preds = selector.predict_collective(op, int(p), b, base_fab,
                                                include_autogen=False)
            if algorithm not in preds:
                raise ValueError(
                    f"calibrate_launch(): no closed form for "
                    f"{op!r}/{algorithm!r} at P={p}")
            rows.append((preds[algorithm],
                         pat.launch_count(op, algorithm, int(p))))
            secs.append(float(seconds))
        a = np.array(rows, dtype=np.float64)
        y = np.array(secs, dtype=np.float64)
        if np.ptp(a[:, 1]) == 0.0:
            raise ValueError(
                "calibrate_launch(): all samples have the same launch "
                "count; mix algorithms/sizes so the per-launch column "
                "is identifiable")
        (c, d), *_ = np.linalg.lstsq(a, y, rcond=None)
        if c <= 0.0:
            raise ValueError(
                "calibrate_launch(): non-positive fitted cycle scale; "
                "timings are noise-dominated -- use larger sizes or "
                "more repeats")
        t_launch = max(float(d / c), 0.0)
        with self._lock:
            new_default = dataclasses.replace(self.topology.default,
                                              t_launch=t_launch)
            new_axes = tuple(
                (axis, dataclasses.replace(f, t_launch=t_launch))
                for axis, f in self.topology.axis_fabrics)
            self.topology = FabricTopology(default=new_default,
                                           axis_fabrics=new_axes,
                                           name=self.topology.name)
            # constants changed => cache namespace moved; in-memory
            # decisions and plans predate the fitted t_launch
            self._decisions.clear()
            self._plans.clear()
            self._tree_rounds.clear()
            self._loaded = False
        return t_launch

    # ------------------------------------------------------------------ #
    # dispatch: *_inside run under an existing shard_map axis binding
    # ------------------------------------------------------------------ #
    @staticmethod
    def _tree_elements(op: str, b: int, p: int) -> int:
        """Vector length the Auto-Gen DP should optimize for: the
        chunked ops run the tree per B/P-element chunk (that is also
        the size their `autogen` prediction was priced at)."""
        if op in ("reduce_scatter", "allgather"):
            return max(1, -(-b // p))
        return b

    def _resolve(self, op: str, nbytes: int, p: int, algorithm: str,
                 axis: Any = None) -> Tuple[str, Optional[Rounds]]:
        """``nbytes`` is always the GLOBAL vector size the cost model is
        written in terms of (callers of allgather pass shard * P).
        ``axis`` (a mesh axis name, or a tuple for a folded logical
        axis) resolves the axis-local fabric on a heterogeneous
        topology."""
        fab = self.topology.for_axis(axis)
        # annotate the innermost open span -- first writer wins, so a
        # nested resolution (allreduce -> reduce) never overwrites the
        # outer op's decision on the outer op's span
        sp = obs_trace.get_tracer().current_span()
        if getattr(sp, "args", {}).get("algorithm") is not None:
            sp = obs_trace.NULL_SPAN
        if algorithm == "auto":
            d, hit = self._select_meta(op, nbytes, p, fabric=fab)
            sp.set(algorithm=d.algorithm, predicted=float(d.predicted),
                   cache="hit" if hit else "miss",
                   regime=("latency" if d.algorithm == "oneshot"
                           else "bandwidth"))
            if d.algorithm == "oneshot":
                with self._lock:
                    self.stats["latency_dispatches"] += 1
            return d.algorithm, d.rounds
        sp.set(algorithm=algorithm, algorithm_forced=True, cache="forced",
               regime="latency" if algorithm == "oneshot" else "bandwidth")
        if algorithm == "oneshot":
            with self._lock:
                self.stats["latency_dispatches"] += 1
        if algorithm in ("autogen", "autogen_pipelined"):
            b = self._tree_elements(op, self._elements(nbytes), p)
            return algorithm, self.tree_rounds(p, b, fabric=fab)
        return algorithm, None

    # ------------------------------------------------------------------ #
    # span plumbing: every public collective opens one CAT_COLLECTIVE
    # span; `_resolve` / `plan_multi` annotate it with the decision
    # ------------------------------------------------------------------ #
    @staticmethod
    def _collective_span(name: str, op: str, axis_or_axes: Any,
                         nbytes: int, algorithm: str):
        """Open a collective span carrying every key the trace schema
        requires (``REQUIRED_COLLECTIVE_ARGS``), so a span is
        conformant even when the op bypasses the model (native/forced
        paths fill the rest in :meth:`_finish_collective`).  With the
        in-memory tracer off it is the span's profiler annotation
        alone."""
        tracer = obs_trace.get_tracer()
        if isinstance(axis_or_axes, (tuple, list)):
            names = tuple(str(a) for a in axis_or_axes)
        else:
            names = (str(axis_or_axes),)
        try:
            sizes = tuple(int(impl._axis_size(a)) for a in names)
        except Exception:
            sizes = ()
        return tracer.span(
            name, cat=obs_trace.CAT_COLLECTIVE, op=op, axes=names,
            axis_sizes=sizes, bytes=int(nbytes),
            requested=str(algorithm), plan=None, algorithm=None,
            cache=None, predicted=None, measured_s=None, mode=None)

    @staticmethod
    def _finish_collective(sp, out: jax.Array, requested: str) -> None:
        """Close out a collective span: paths that never reached the
        model (native XLA ops, identity axes) stamp the requested
        algorithm as forced, then the result stamps mode/wall time."""
        span = getattr(sp, "span", None)
        if span is not None and span.args.get("algorithm") is None:
            sp.set(algorithm=str(requested), algorithm_forced=True,
                   cache="forced")
        sp.finish_result(out)

    def reduce_inside(self, x: jax.Array, axis: str,
                      algorithm: str = "auto") -> jax.Array:
        """Paper Reduce: full sum lands on device 0 of the axis."""
        p = impl._axis_size(axis)
        if p == 1:
            return x
        algorithm, rounds = self._resolve("reduce", x.size * x.dtype.itemsize,
                                          p, algorithm, axis)
        if algorithm == "chain":
            return impl.chain_reduce(x, axis)
        if algorithm == "tree":
            return impl.tree_reduce(x, axis)
        if algorithm == "two_phase":
            return impl.two_phase_reduce(x, axis)
        if algorithm == "star":
            return impl.star_reduce(x, axis)
        if algorithm == "autogen":
            return impl.schedule_reduce(x, axis, rounds)
        if algorithm == "autogen_pipelined":
            flat = x.reshape(-1)
            out = impl.schedule_reduce_pipelined(flat, axis, rounds)
            return out.reshape(x.shape)
        raise ValueError(f"unknown reduce algorithm {algorithm!r}")

    def allreduce_inside(self, x: jax.Array, axis: str,
                         algorithm: str = "auto") -> jax.Array:
        if not obs_trace.get_tracer().enabled:
            return self._allreduce_inside(x, axis, algorithm)
        with self._collective_span("allreduce_inside", "allreduce", axis,
                                   x.size * x.dtype.itemsize,
                                   algorithm) as sp:
            out = self._allreduce_inside(x, axis, algorithm)
            self._finish_collective(sp, out, algorithm)
            return out

    def _allreduce_inside(self, x: jax.Array, axis: str,
                          algorithm: str = "auto") -> jax.Array:
        if algorithm == "psum":
            return lax.psum(x, axis)
        p = impl._axis_size(axis)
        if p == 1:
            return x
        algorithm, rounds = self._resolve(
            "allreduce", x.size * x.dtype.itemsize, p, algorithm, axis)
        if algorithm == "oneshot":
            # the latency regime: one fused XLA program over the (possibly
            # folded) axis -- depth 1, a single launch, no staging
            return lax.psum(x, axis)
        if algorithm == "ring":
            flat = x.reshape(-1)
            return impl.ring_allreduce(flat, axis).reshape(x.shape)
        red = self.reduce_inside(x, axis, algorithm)
        return impl.broadcast(red, axis, root=0)

    def reduce_scatter_inside(self, x: jax.Array, axis: str,
                              algorithm: str = "auto") -> jax.Array:
        """Sum over the axis, shard the result: device i gets chunk i
        (``lax.psum_scatter(..., tiled=True)`` semantics; leading dim
        divisible by P)."""
        if not obs_trace.get_tracer().enabled:
            return self._reduce_scatter_inside(x, axis, algorithm)
        with self._collective_span("reduce_scatter_inside",
                                   "reduce_scatter", axis,
                                   x.size * x.dtype.itemsize,
                                   algorithm) as sp:
            out = self._reduce_scatter_inside(x, axis, algorithm)
            self._finish_collective(sp, out, algorithm)
            return out

    def _reduce_scatter_inside(self, x: jax.Array, axis: str,
                               algorithm: str = "auto") -> jax.Array:
        p = impl._axis_size(axis)
        if p == 1:
            return x
        if algorithm != "psum_scatter":
            algorithm, rounds = self._resolve(
                "reduce_scatter", x.size * x.dtype.itemsize, p, algorithm,
                axis)
        if algorithm == "psum_scatter":
            return lax.psum_scatter(x, axis, scatter_dimension=0,
                                    tiled=True)
        if algorithm == "ring":
            return impl.reduce_scatter_ring(x, axis)
        if algorithm == "autogen":
            return impl.schedule_reduce_scatter(x, axis, rounds)
        raise ValueError(f"unknown reduce_scatter algorithm {algorithm!r}")

    def allgather_inside(self, x: jax.Array, axis: str,
                         algorithm: str = "auto") -> jax.Array:
        """Gather shards along the axis into the leading dim
        (``lax.all_gather(..., tiled=True)`` semantics)."""
        if not obs_trace.get_tracer().enabled:
            return self._allgather_inside(x, axis, algorithm)
        # the span (like the cost model) records the GLOBAL gathered
        # bytes, shard * P -- the replayer relies on this convention
        nbytes = x.size * x.dtype.itemsize * impl._axis_size(axis)
        with self._collective_span("allgather_inside", "allgather",
                                   axis, nbytes, algorithm) as sp:
            out = self._allgather_inside(x, axis, algorithm)
            self._finish_collective(sp, out, algorithm)
            return out

    def _allgather_inside(self, x: jax.Array, axis: str,
                          algorithm: str = "auto") -> jax.Array:
        p = impl._axis_size(axis)
        if p == 1:
            return x
        if algorithm != "all_gather":
            # x is the local shard; the cost model prices the global
            # gather, so scale by P
            algorithm, rounds = self._resolve(
                "allgather", x.size * x.dtype.itemsize * p, p, algorithm,
                axis)
        if algorithm in ("all_gather", "oneshot"):
            # "oneshot" is the latency-regime selection of the same
            # single-program gather ("all_gather" is the forced native
            # path that bypasses the model)
            return lax.all_gather(x, axis, tiled=True)
        if algorithm == "ring":
            return impl.allgather_ring(x, axis)
        if algorithm == "doubling":
            return impl.allgather_doubling(x, axis)
        if algorithm == "autogen":
            return impl.schedule_allgather(x, axis, rounds)
        raise ValueError(f"unknown allgather algorithm {algorithm!r}")

    def all_to_all_inside(self, x: jax.Array, axis, algorithm: str = "auto"
                          ) -> jax.Array:
        """Personalized exchange along one axis (or a row-major-folded
        axis tuple): ``lax.all_to_all(x, axis, split_axis=0,
        concat_axis=0, tiled=True)`` semantics -- x is [P*m, ...] with
        destination-major leading chunks, the result source-major.
        ``algorithm``: ``lax`` (XLA native), ``ring``
        (pairwise-exchange, injection-optimal), ``halving`` (Bruck,
        log-launch), or ``auto`` (model argmin)."""
        if not obs_trace.get_tracer().enabled:
            return self._all_to_all_inside(x, axis, algorithm)
        with self._collective_span("all_to_all_inside", "all_to_all",
                                   axis, x.size * x.dtype.itemsize,
                                   algorithm) as sp:
            out = self._all_to_all_inside(x, axis, algorithm)
            self._finish_collective(sp, out, algorithm)
            return out

    def _all_to_all_inside(self, x: jax.Array, axis,
                           algorithm: str = "auto") -> jax.Array:
        p = impl._axis_size(axis)
        if p == 1:
            return x
        if algorithm == "lax":
            return lax.all_to_all(x, axis, split_axis=0, concat_axis=0,
                                  tiled=True)
        algorithm, _ = self._resolve(
            "all_to_all", x.size * x.dtype.itemsize, p, algorithm, axis)
        if algorithm == "oneshot":
            return lax.all_to_all(x, axis, split_axis=0, concat_axis=0,
                                  tiled=True)
        if algorithm == "ring":
            return impl.all_to_all_ring(x, axis)
        if algorithm == "halving":
            return impl.all_to_all_bruck(x, axis)
        raise ValueError(f"unknown all_to_all algorithm {algorithm!r}")

    def broadcast_inside(self, x: jax.Array, axis: str, root: int = 0,
                         algorithm: str = "auto") -> jax.Array:
        p = impl._axis_size(axis)
        if p == 1:
            return x
        algorithm, rounds = self._resolve(
            "broadcast", x.size * x.dtype.itemsize, p, algorithm, axis)
        if algorithm == "doubling":
            return impl.broadcast(x, axis, root=root)
        if algorithm == "chain":
            return impl.chain_broadcast(x, axis, root=root)
        if algorithm == "autogen":
            if root != 0:
                rounds = impl._rotate_rounds(rounds, p, root)
            seeded = jnp.where(impl._axis_index(axis) == root, x,
                               jnp.zeros_like(x))
            return impl.schedule_broadcast(seeded, axis, rounds)
        raise ValueError(f"unknown broadcast algorithm {algorithm!r}")

    # ------------------------------------------------------------------ #
    # multi-axis dispatch: planner-driven joint plans over axis tuples
    # ------------------------------------------------------------------ #
    @staticmethod
    def _multi_sizes(axes: Sequence[str]) -> Tuple[int, ...]:
        return tuple(impl._axis_size(a) for a in axes)

    @staticmethod
    def _chunk_transpose(x: jax.Array, sizes: Sequence[int]) -> jax.Array:
        """Reorder leading-dim chunks from row-major blocks over
        ``sizes`` to row-major blocks over ``reversed(sizes)`` -- the
        permutation that makes the innermost-first reduce-scatter
        cascade land chunks in ``lax.psum_scatter`` (device-major)
        order."""
        k = len(sizes)
        blocks = x.reshape(tuple(sizes) + (-1,) + x.shape[1:])
        perm = tuple(reversed(range(k))) + tuple(range(k, blocks.ndim))
        return blocks.transpose(perm).reshape(x.shape)

    # ------------------------------------------------------------------ #
    # chunked phase-runner: one wavefront executor for every plan
    # ------------------------------------------------------------------ #
    @staticmethod
    def _phase_names(steps: Sequence["planner.PlanStep"]) -> List[str]:
        """Human labels for a plan's phases, mirroring
        ``CollectivePlan.describe()`` per step."""
        return [
            f"{planner._KIND_ABBREV.get(s.kind, s.kind)}:"
            f"{s.algorithm}@{'x'.join(s.axes)}"
            for s in steps]

    def _run_phases(self, chunks: List[jax.Array],
                    phase_fns: Sequence[Callable[[jax.Array], jax.Array]],
                    op: Optional[str] = None,
                    phase_names: Optional[Sequence[str]] = None
                    ) -> List[jax.Array]:
        """Execute ``phase_fns`` over payload ``chunks`` as a wavefront
        pipeline: in wave ``w``, chunk ``k`` runs phase ``w - k`` -- so
        chunk 0's outer (cross-pod) phase is issued alongside chunk 1's
        inner phase, and phases on disjoint link classes overlap.  The
        chunks are data-independent, so nothing in the emitted program
        orders one chunk's phase after another chunk's; the compiler is
        free to run them concurrently.  With a single chunk this
        degenerates to running the phases back-to-back -- the
        serialized plan executor, shared by every plan shape.

        With tracing enabled each phase call is wrapped in a
        ``jax.named_scope`` (so an XLA profile lines up with the plan's
        phase decomposition) and emits a nested CAT_PHASE span; phase
        spans never block, whatever the tracer's measurement mode."""
        tracer = obs_trace.get_tracer()
        chunks = list(chunks)
        n = len(phase_fns)
        for wave in range(n + len(chunks) - 1):
            for k in range(len(chunks)):
                r = wave - k
                if not 0 <= r < n:
                    continue
                if not tracer.enabled:
                    chunks[k] = phase_fns[r](chunks[k])
                    continue
                label = (phase_names[r]
                         if phase_names and r < len(phase_names)
                         else f"phase{r}")
                scope = f"{op or 'collective'}.{label}".replace(":", "_")
                with jax.named_scope(scope), \
                        tracer.span(label, cat=obs_trace.CAT_PHASE,
                                    op=op, phase=r, chunk=k,
                                    wave=wave) as sp:
                    chunks[k] = phase_fns[r](chunks[k])
                    sp.finish_result(chunks[k], block=False)
        return chunks

    @staticmethod
    def _split_row_chunks(x: jax.Array, p: int, c: int
                          ) -> Tuple[List[jax.Array], int]:
        """Slice ``x`` ([p*m, ...], p row-major device blocks) into
        ``c`` chunks of ``[p*mc, ...]``, chunk ``k`` carrying rows
        ``[k*mc, (k+1)*mc)`` of every device block (zero-padded when
        ``c`` does not divide m).  Returns the chunks and m."""
        m = x.shape[0] // p
        mc = ceil_div(m, c)
        blocks = x.reshape((p, m) + x.shape[1:])
        pad = c * mc - m
        if pad:
            widths = [(0, 0)] * blocks.ndim
            widths[1] = (0, pad)
            blocks = jnp.pad(blocks, widths)
        return [blocks[:, k * mc:(k + 1) * mc].reshape(
                    (p * mc,) + x.shape[1:])
                for k in range(c)], m

    @staticmethod
    def _join_row_chunks(chunks: List[jax.Array], p: int, m: int
                         ) -> jax.Array:
        """Inverse of :meth:`_split_row_chunks` on the output side:
        chunk ``k`` holds rows ``[k*mc, (k+1)*mc)`` of every device
        block of the [p*m, ...] result (pad rows dropped)."""
        mc = chunks[0].shape[0] // p
        trailing = chunks[0].shape[1:]
        parts = [ch.reshape((p, mc) + trailing) for ch in chunks]
        out = jnp.concatenate(parts, axis=1)[:, :m]
        return out.reshape((p * m,) + trailing)

    def allreduce_multi(self, x: jax.Array, axes: Sequence[str],
                        algorithm: str = "auto") -> jax.Array:
        """AllReduce over an axis tuple through a joint topology plan.

        ``algorithm`` is either ``"auto"`` (planner argmin), a plan
        shape (``"sequential" | "hierarchical" | "2d_xy" | "2d_snake" |
        "flat"`` or a ``*_pipelined`` variant, executed chunked over
        ``plan.n_chunks`` payload slices), ``"psum"`` (XLA native over
        the folded axes), or a 1D backend name, which forces the
        sequential shape with that backend on every axis (the legacy
        per-axis loop).
        """
        axes = tuple(axes)
        if len(axes) == 1:
            return self.allreduce_inside(x, axes[0], algorithm)
        if not obs_trace.get_tracer().enabled:
            return self._allreduce_multi(x, axes, algorithm)
        with self._collective_span("allreduce_multi", "allreduce", axes,
                                   x.size * x.dtype.itemsize,
                                   algorithm) as sp:
            out = self._allreduce_multi(x, axes, algorithm)
            self._finish_collective(sp, out, algorithm)
            return out

    def _allreduce_multi(self, x: jax.Array, axes: Tuple[str, ...],
                         algorithm: str) -> jax.Array:
        if algorithm == "psum":
            return lax.psum(x, axes)
        sizes = self._multi_sizes(axes)
        if all(s == 1 for s in sizes):
            return x
        nbytes = x.size * x.dtype.itemsize
        if algorithm == "auto" or algorithm in planner.ALLREDUCE_SHAPES:
            shape = None if algorithm == "auto" else algorithm
            plan = self.plan_multi("allreduce", axes, sizes, nbytes,
                                   shape=shape)
            return self._run_allreduce_plan(x, plan)
        # legacy: explicit 1D backend, innermost axis first
        for ax in reversed(axes):
            x = self.allreduce_inside(x, ax, algorithm)
        return x

    def _run_allreduce_plan(self, x: jax.Array,
                            plan: "planner.CollectivePlan") -> jax.Array:
        if plan.shape == "identity":
            return x
        if plan.shape == "2d_xy":
            (step,) = plan.steps
            patterns = tuple(step.algorithm.split("x"))
            return impl.xy_allreduce_2d(x, step.axes, patterns)
        if plan.shape == "2d_snake":
            (step,) = plan.steps
            return impl.snake_allreduce_2d(x, step.axes)
        if plan.shape in ("flat", "latency"):
            # both are one step over the folded axis tuple; "latency"
            # carries the "oneshot" algorithm, dispatched as a single
            # fused XLA collective (no chunking, no cascade)
            (step,) = plan.steps
            return self.allreduce_inside(x, step.axes, step.algorithm)
        base = planner.base_shape(plan.shape)
        if base not in ("sequential", "hierarchical"):
            raise ValueError(f"unknown plan shape {plan.shape!r}")
        shape0 = x.shape
        flat = x.reshape(-1)
        n = flat.size
        c = max(1, plan.n_chunks)
        chunk_len = ceil_div(n, c)
        pad = c * chunk_len - n
        if pad:
            flat = jnp.pad(flat, (0, pad))
        chunks = [flat[k * chunk_len:(k + 1) * chunk_len]
                  for k in range(c)]
        fns = self._allreduce_phase_fns(plan, base, chunk_len)
        chunks = self._run_phases(chunks, fns, op="allreduce",
                                  phase_names=self._phase_names(plan.steps))
        out = jnp.concatenate(chunks) if c > 1 else chunks[0]
        if pad:
            out = out[:n]
        return out.reshape(shape0)

    def _allreduce_phase_fns(self, plan: "planner.CollectivePlan",
                             base: str, chunk_len: int
                             ) -> List[Callable[[jax.Array], jax.Array]]:
        """Per-phase closures mapping a flat ``[chunk_len]`` slice to
        its reduced ``[chunk_len]`` -- the executable form of a
        sequential or hierarchical allreduce plan, fed to
        :meth:`_run_phases`."""
        if base == "sequential":
            return [
                (lambda v, s=step: self.allreduce_inside(
                    v, s.axes[0], s.algorithm))
                for step in plan.steps]
        rs, mid, ag = plan.steps
        inner = rs.axes[0]
        p_in = impl._axis_size(inner)
        pad = (-chunk_len) % p_in

        def f_rs(v):
            if pad:
                v = jnp.pad(v, (0, pad))
            return self.reduce_scatter_inside(v, inner,
                                              algorithm=rs.algorithm)

        def f_mid(v):
            return self.allreduce_multi(v, mid.axes,
                                        algorithm=mid.algorithm)

        def f_ag(v):
            v = self.allgather_inside(v, inner, algorithm=ag.algorithm)
            return v[:chunk_len] if pad else v

        return [f_rs, f_mid, f_ag]

    def reduce_scatter_multi(self, x: jax.Array, axes: Sequence[str],
                             algorithm: str = "auto") -> jax.Array:
        """Sum over the folded axes, shard the result device-major
        (``lax.psum_scatter(x, axes, tiled=True)`` semantics; leading
        dim divisible by the folded size)."""
        axes = tuple(axes)
        if len(axes) == 1:
            return self.reduce_scatter_inside(x, axes[0], algorithm)
        if not obs_trace.get_tracer().enabled:
            return self._reduce_scatter_multi(x, axes, algorithm)
        with self._collective_span("reduce_scatter_multi",
                                   "reduce_scatter", axes,
                                   x.size * x.dtype.itemsize,
                                   algorithm) as sp:
            out = self._reduce_scatter_multi(x, axes, algorithm)
            self._finish_collective(sp, out, algorithm)
            return out

    def _reduce_scatter_multi(self, x: jax.Array, axes: Tuple[str, ...],
                              algorithm: str) -> jax.Array:
        if algorithm == "psum_scatter":
            return lax.psum_scatter(x, axes, scatter_dimension=0,
                                    tiled=True)
        sizes = self._multi_sizes(axes)
        p = 1
        for s in sizes:
            p *= s
        if p == 1:
            return x
        assert x.shape[0] % p == 0, (x.shape, p)
        nbytes = x.size * x.dtype.itemsize
        shape = None if algorithm == "auto" else algorithm
        plan = self.plan_multi("reduce_scatter", axes, sizes, nbytes,
                               shape=shape)
        if plan.shape == "flat":
            (step,) = plan.steps
            return self.reduce_scatter_inside(x, step.axes,
                                              step.algorithm)
        # cascade: pre-permute chunks so the innermost-first shrink
        # lands each device on its psum_scatter chunk; a pipelined plan
        # slices each device block's rows and wavefronts the phases
        steps = plan.steps

        def f0(v, s=steps[0]):
            return self.reduce_scatter_inside(
                self._chunk_transpose(v, sizes), s.axes[0], s.algorithm)

        fns = [f0] + [
            (lambda v, s=step: self.reduce_scatter_inside(
                v, s.axes[0], s.algorithm))
            for step in steps[1:]]
        names = self._phase_names(steps)
        c = max(1, plan.n_chunks)
        if c == 1:
            return self._run_phases([x], fns, op="reduce_scatter",
                                    phase_names=names)[0]
        chunks, m = self._split_row_chunks(x, p, c)
        chunks = self._run_phases(chunks, fns, op="reduce_scatter",
                                  phase_names=names)
        return jnp.concatenate(chunks, axis=0)[:m]

    def allgather_multi(self, x: jax.Array, axes: Sequence[str],
                        algorithm: str = "auto") -> jax.Array:
        """Gather device-major shards along the folded axes into the
        leading dim (``lax.all_gather(x, axes, tiled=True)``
        semantics)."""
        axes = tuple(axes)
        if len(axes) == 1:
            return self.allgather_inside(x, axes[0], algorithm)
        if not obs_trace.get_tracer().enabled:
            return self._allgather_multi(x, axes, algorithm)
        # global gathered bytes, matching the model's B and the replayer
        nbytes = x.size * x.dtype.itemsize * impl._axis_size(axes)
        with self._collective_span("allgather_multi", "allgather", axes,
                                   nbytes, algorithm) as sp:
            out = self._allgather_multi(x, axes, algorithm)
            self._finish_collective(sp, out, algorithm)
            return out

    def _allgather_multi(self, x: jax.Array, axes: Tuple[str, ...],
                         algorithm: str) -> jax.Array:
        if algorithm == "all_gather":
            return lax.all_gather(x, axes, tiled=True)
        sizes = self._multi_sizes(axes)
        p = 1
        for s in sizes:
            p *= s
        if p == 1:
            return x
        nbytes = x.size * x.dtype.itemsize * p
        shape = None if algorithm == "auto" else algorithm
        plan = self.plan_multi("allgather", axes, sizes, nbytes,
                               shape=shape)
        if plan.shape in ("flat", "latency"):
            (step,) = plan.steps
            return self.allgather_inside(x, step.axes, step.algorithm)
        # cascade: outermost-first growth, then undo the chunk
        # permutation the matching reduce-scatter cascade applied; a
        # pipelined plan slices the shard's rows and wavefronts
        steps = plan.steps

        def f_last(v, s=steps[-1]):
            return self._chunk_transpose(
                self.allgather_inside(v, s.axes[0], s.algorithm),
                tuple(reversed(sizes)))

        fns = [
            (lambda v, s=step: self.allgather_inside(
                v, s.axes[0], s.algorithm))
            for step in steps[:-1]] + [f_last]
        names = self._phase_names(steps)
        c = max(1, plan.n_chunks)
        if c == 1:
            return self._run_phases([x], fns, op="allgather",
                                    phase_names=names)[0]
        s_len = x.shape[0]
        sc = ceil_div(s_len, c)
        pad = c * sc - s_len
        xp = x
        if pad:
            widths = [(0, 0)] * x.ndim
            widths[0] = (0, pad)
            xp = jnp.pad(x, widths)
        chunks = [xp[k * sc:(k + 1) * sc] for k in range(c)]
        chunks = self._run_phases(chunks, fns, op="allgather",
                                  phase_names=names)
        return self._join_row_chunks(chunks, p, s_len)

    def all_to_all_multi(self, x: jax.Array, axes: Sequence[str],
                         algorithm: str = "auto") -> jax.Array:
        """Personalized exchange over an axis tuple through a joint
        topology plan (``lax.all_to_all(x, axes, 0, 0, tiled=True)``
        semantics over the row-major-folded axes).

        ``algorithm`` is ``"auto"`` (planner argmin), a plan shape
        (``"hierarchical" | "sequential" | "flat"`` or a
        ``*_pipelined`` variant, executed chunked over
        ``plan.n_chunks`` payload slices), ``"lax"`` (XLA native
        single-shot over the folded axes), or a 1D backend name
        (``ring``/``halving``), which forces the hierarchical
        (innermost-first) phase order with that backend on every axis.
        """
        axes = tuple(axes)
        if len(axes) == 1:
            # a plan shape collapses to the 1D selector on a bare axis
            if algorithm in planner.ALL_TO_ALL_SHAPES:
                algorithm = "auto"
            return self.all_to_all_inside(x, axes[0], algorithm)
        if not obs_trace.get_tracer().enabled:
            return self._all_to_all_multi(x, axes, algorithm)
        with self._collective_span("all_to_all_multi", "all_to_all",
                                   axes, x.size * x.dtype.itemsize,
                                   algorithm) as sp:
            out = self._all_to_all_multi(x, axes, algorithm)
            self._finish_collective(sp, out, algorithm)
            return out

    def _all_to_all_multi(self, x: jax.Array, axes: Tuple[str, ...],
                          algorithm: str) -> jax.Array:
        if algorithm == "lax":
            return lax.all_to_all(x, axes, split_axis=0, concat_axis=0,
                                  tiled=True)
        sizes = self._multi_sizes(axes)
        p = 1
        for s in sizes:
            p *= s
        if p == 1:
            return x
        assert x.shape[0] % p == 0, (x.shape, p)
        nbytes = x.size * x.dtype.itemsize
        if algorithm == "auto" or algorithm in planner.ALL_TO_ALL_SHAPES:
            shape = None if algorithm == "auto" else algorithm
            plan = self.plan_multi("all_to_all", axes, sizes, nbytes,
                                   shape=shape)
            if plan.shape in ("flat", "latency"):
                (step,) = plan.steps
                return self.all_to_all_inside(x, step.axes,
                                              step.algorithm)
            return self._run_a2a_phases(x, axes, sizes, plan.steps,
                                        plan.n_chunks)
        # legacy: explicit 1D backend on every axis, innermost first
        steps = tuple(
            planner.PlanStep("all_to_all", (a,), algorithm, nbytes)
            for a, s in zip(reversed(axes), reversed(sizes)) if s > 1)
        return self._run_a2a_phases(x, axes, sizes, steps)

    def _a2a_phase_fns(self, axes: Tuple[str, ...],
                       sizes: Tuple[int, ...],
                       steps: Sequence["planner.PlanStep"]
                       ) -> List[Callable[[jax.Array], jax.Array]]:
        """Per-phase closures over the block grid.  Each closure views
        its input's leading dim as a ``sizes``-shaped grid of blocks
        and exchanges along block dim *i* only, turning that
        destination coordinate into the source coordinate in place --
        self-contained per step, so any chunk size divisible into the
        grid runs the same way."""
        k = len(sizes)
        p = 1
        for s in sizes:
            p *= s

        def make(step):
            i = axes.index(step.axes[0])
            perm = ((i,) + tuple(j for j in range(k) if j != i))
            inv = tuple(int(j) for j in np.argsort(perm))

            def fn(v):
                m = v.shape[0] // p
                blocks = v.reshape(tuple(sizes) + (m,) + v.shape[1:])
                full_perm = perm + tuple(range(k, blocks.ndim))
                t = blocks.transpose(full_perm)
                flat = t.reshape((-1,) + v.shape[1:])
                out = self.all_to_all_inside(flat, step.axes[0],
                                             algorithm=step.algorithm)
                full_inv = inv + tuple(range(k, blocks.ndim))
                return out.reshape(t.shape).transpose(full_inv).reshape(
                    v.shape)

            return fn

        return [make(step) for step in steps]

    def _run_a2a_phases(self, x: jax.Array, axes: Tuple[str, ...],
                        sizes: Tuple[int, ...],
                        steps: Sequence["planner.PlanStep"],
                        n_chunks: int = 1) -> jax.Array:
        """Execute per-axis all-to-all phases over the block grid.

        The leading dim is viewed as a ``sizes``-shaped grid of blocks
        (destination-major).  A phase on axis *i* exchanges along block
        dim *i* only, turning that destination coordinate into the
        source coordinate in place -- so after every effective axis has
        run once (any order), the block grid is source-major row-major,
        exactly ``lax.all_to_all`` over the folded tuple.  With
        ``n_chunks > 1`` each block contributes a row slice per chunk
        and the phases run as a wavefront pipeline."""
        fns = self._a2a_phase_fns(axes, sizes, steps)
        names = self._phase_names(steps)
        c = max(1, n_chunks)
        if c == 1:
            return self._run_phases([x], fns, op="all_to_all",
                                    phase_names=names)[0]
        p = 1
        for s in sizes:
            p *= s
        chunks, m = self._split_row_chunks(x, p, c)
        chunks = self._run_phases(chunks, fns, op="all_to_all",
                                  phase_names=names)
        return self._join_row_chunks(chunks, p, m)

    # ------------------------------------------------------------------ #
    # fused compute + collective: matmul feeding a ring reduce-scatter
    # ------------------------------------------------------------------ #
    def price_fused_matmul_rs(self, m: int, k: int, n: int, p: int,
                              axes: Any = None, dtype_bytes: int = 4
                              ) -> Dict[str, float]:
        """Model prices for the fused vs serialized matmul+RS.

        ``[m, k] @ [k, n]`` per device, reduce-scattered over a P-way
        axis (``axes`` resolves the fabric on a heterogeneous topology;
        a tuple folds to the slowest member, as the planner prices flat
        phases).  ``fused`` is the PR 6 overlap closed form with C = P
        chunks (``patterns.t_fused_matmul_rs``); ``serial`` is the full
        GEMM followed by the best cached reduce-scatter decision.
        ``saved`` > 0 is the model saying the block GEMMs are long
        enough to hide the ring hops -- the bit ``"auto"`` dispatch
        acts on."""
        if isinstance(axes, (tuple, list)):
            axes = tuple(axes)
        fab = self.topology.for_axis(axes)
        nbytes = int(m) * int(n) * int(dtype_bytes)
        t_mm = pat.t_matmul(m, k, n)
        fused = pat.t_fused_matmul_rs(p, self._elements(nbytes), t_mm,
                                      fab)
        rs = self.select("reduce_scatter", nbytes, p, fabric=fab)
        serial = t_mm + rs.predicted
        return {"fused": float(fused), "serial": float(serial),
                "saved": float(serial - fused), "t_mm": float(t_mm),
                "t_rs": float(rs.predicted)}

    def fused_matmul_reduce_scatter(self, x: jax.Array,
                                    w: Optional[jax.Array], axes, *,
                                    algorithm: str = "auto",
                                    block_m: Optional[int] = None,
                                    block_n: Optional[int] = None,
                                    interpret: Optional[bool] = None
                                    ) -> jax.Array:
        """``reduce_scatter(x @ w)`` over ``axes`` with the GEMM tiles
        overlapping the ring's wire time, run inside shard_map.

        ``x``: local ``[M, K_loc]``; ``w``: local ``[K_loc, N]`` (the
        contraction dim sharded over ``axes``); returns ``[M/P, N]``
        with device ``i`` holding row block ``i`` of the summed product
        (``lax.psum_scatter(..., tiled=True)`` semantics).

        ``algorithm``: ``"auto"`` runs the fused ring exactly when the
        model prices it below the serialized GEMM-then-RS
        (:meth:`price_fused_matmul_rs`); ``"fused"`` / ``"unfused"``
        force either path.  ``w=None`` means the call site has no local
        GEMM to fuse (the FSDP grad-sync reduce-scatter) and the call
        degenerates to the engine's chunk-overlapped reduce-scatter
        over the same axes -- the same opt-in flag covers both sites.
        Shapes the ring cannot tile (M not divisible by P) fall back to
        the gathered path.  ``interpret=None`` takes the backend's Pallas
        mode (``kernels.ops.default_interpret``)."""
        from repro.kernels import fused_matmul_rs as fk
        axes = (axes,) if isinstance(axes, str) else tuple(axes)
        if w is None:
            if len(axes) == 1:
                return self.reduce_scatter_inside(x, axes[0], algorithm)
            return self.reduce_scatter_multi(x, axes, algorithm)
        axis = axes[0] if len(axes) == 1 else axes
        p = impl._axis_size(axis)
        m, k = x.shape
        n = w.shape[-1]
        price = self.price_fused_matmul_rs(
            m, k, n, p, axes=axis, dtype_bytes=x.dtype.itemsize)
        if algorithm == "fused":
            use_fused = True
        elif algorithm == "unfused":
            use_fused = False
        else:
            use_fused = price["saved"] > 0.0
        if m % max(p, 1) != 0:
            use_fused = False       # ring cannot tile the rows
        kwargs: Dict[str, Any] = {"interpret": interpret}
        if block_m is not None:
            kwargs["block_m"] = block_m
        if block_n is not None:
            kwargs["block_n"] = block_n

        def run() -> jax.Array:
            if use_fused:
                return fk.fused_matmul_rs(x, w, axis, **kwargs)
            return fk.matmul_then_rs(x, w, axis)

        if not obs_trace.get_tracer().enabled:
            return run()
        with self._collective_span("fused_matmul_rs", "fused_matmul_rs",
                                   axes, m * n * x.dtype.itemsize,
                                   algorithm) as sp:
            sp.set(algorithm="fused_ring" if use_fused else "unfused",
                   algorithm_forced=algorithm != "auto", cache="model",
                   predicted=price["fused" if use_fused else "serial"],
                   overlap_saved=price["saved"])
            out = run()
            self._finish_collective(sp, out, algorithm)
            return out

    # ------------------------------------------------------------------ #
    # outer wrappers: build the shard_map for replicated operands
    # ------------------------------------------------------------------ #
    def _wrap(self, fn: Callable[[jax.Array], jax.Array], mesh: Mesh,
              in_spec: P, out_spec: P) -> Callable[[jax.Array], jax.Array]:
        return shard_map(fn, mesh=mesh, in_specs=in_spec,
                         out_specs=out_spec, check_vma=False)

    def allreduce(self, x: jax.Array, mesh: Mesh, axis: str,
                  algorithm: str = "auto") -> jax.Array:
        fn = lambda v: self.allreduce_inside(v, axis, algorithm)
        return self._wrap(fn, mesh, P(), P())(x)

    def reduce_to_root(self, x: jax.Array, mesh: Mesh, axis: str,
                       algorithm: str = "auto") -> jax.Array:
        fn = lambda v: self.reduce_inside(v, axis, algorithm)
        return self._wrap(fn, mesh, P(), P())(x)

    def reduce_scatter(self, x: jax.Array, mesh: Mesh, axis: str,
                       algorithm: str = "auto") -> jax.Array:
        """x replicated [N, ...] -> global [N, ...] summed over the axis,
        laid out sharded along it (device i holds chunk i)."""
        fn = lambda v: self.reduce_scatter_inside(v, axis, algorithm)
        return self._wrap(fn, mesh, P(), P(axis))(x)

    def allgather(self, x: jax.Array, mesh: Mesh, axis: str,
                  algorithm: str = "auto") -> jax.Array:
        """x sharded [N, ...] along the axis -> replicated [N, ...]."""
        fn = lambda v: self.allgather_inside(v, axis, algorithm)
        return self._wrap(fn, mesh, P(axis), P())(x)

    def broadcast(self, x: jax.Array, mesh: Mesh, axis: str, root: int = 0,
                  algorithm: str = "auto") -> jax.Array:
        fn = lambda v: self.broadcast_inside(v, axis, root, algorithm)
        return self._wrap(fn, mesh, P(), P())(x)

    def all_to_all(self, x: jax.Array, mesh: Mesh, axis: str,
                   algorithm: str = "auto") -> jax.Array:
        """x sharded [N, ...] along the axis (N a multiple of P*P): each
        device's local [N/P, ...] block is exchanged chunk-for-chunk --
        the distributed transpose."""
        fn = lambda v: self.all_to_all_inside(v, axis, algorithm)
        return self._wrap(fn, mesh, P(axis), P(axis))(x)


__all__ = ["CollectiveEngine", "Decision", "fit_fabric",
           "measure_ppermute", "load_topology", "find_calibrated_topology",
           "topology_to_dict",
           "topology_from_dict", "fabric_to_dict", "SCHEMA_VERSION",
           "ICI_ELEMENT_BYTES"]

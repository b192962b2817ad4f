"""Program-level performance observatory: XLA cost accounting + rooflines.

runtimestats answers "how long did each device step take"; nothing in the
stack answered "how much work did XLA *compile into* that step, and what
fraction of the chip's roofline did the warm path achieve".  This module
closes that gap (docs/OBSERVABILITY.md "Program catalog & roofline"):

- the engine's compile sites (the ``_compiled_steps`` census in
  ``engine/classify.py`` plus the packed/quant/kernel/mesh rebuild paths)
  call :meth:`ProgramCatalog.note_compile` with a zero-argument *lower
  thunk* — capture is deferred, so the serving hot path only pays one
  dict insert of abstract shapes, never an extra XLA compile;
- :meth:`ProgramCatalog.capture_pending` (run at catalog-read time:
  ``GET /debug/programs``, ``make perfgate``, bench, SLO-burn capture)
  executes ``lower().compile()`` ahead-of-time and records
  ``cost_analysis()`` (flops, bytes accessed) + ``memory_analysis()``
  (argument/output/temp bytes — the program's HBM footprint) per program
  key ``(group, bucket, variant, quant, kernels, mesh)``;
- :meth:`ProgramCatalog.catalog` joins the cost model with the
  runtimestats warm-execute EWMAs and token-fill ratios into
  achieved-FLOP/s, achieved-bytes/s and roofline-fraction rows against a
  per-device peak table (v5e and friends from public datasheets; the CPU
  tier is an order-of-magnitude placeholder and every CPU row says so),
  published as ``llm_program_{flops,bytes,hbm_peak_bytes,
  roofline_fraction}`` gauges;
- :class:`SLOCaptureController` arms SLO-burn-triggered automatic
  capture: a firing ``slo_alert_firing`` event starts ONE bounded
  ``ProfilerControl`` trace + a program-catalog snapshot (cooldown-gated,
  ring-bounded), cross-linked from the flight recorder dump.

Failure posture: capture is fail-open everywhere.  A backend without
``cost_analysis`` support, a donated-buffer lowering quirk, or a changed
jit signature records an ``error`` row — it never breaks serving, and it
never raises past the catalog.
"""

from __future__ import annotations

import threading
import time
from collections import deque
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional, Tuple

# -- per-device peak table ----------------------------------------------------
#
# (substring-of-device_kind, tier) — first match wins.  TPU numbers are
# the public datasheet peaks (dense bf16 MXU FLOP/s, HBM bandwidth, HBM
# capacity).  The CPU tier exists so roofline math stays total on dev
# rigs, but it is an order-of-magnitude guess about an unknown host —
# rows carry ``peak_note`` saying exactly that, and CPU fractions must
# never be compared across machines.  An ACCELERATOR that is not in the
# table gets the "unknown" tier: no peaks, so no roofline fractions —
# never a guess dressed as a measurement.
_PEAK_TIERS: Tuple[Tuple[Tuple[str, ...], Dict[str, Any]], ...] = (
    (("v6e", "trillium"), {
        "tier": "tpu-v6e", "flops_per_s": 918e12,
        "hbm_bytes_per_s": 1640e9, "hbm_bytes": 32 * 2**30,
        "peak_note": "TPU v6e datasheet: 918 TFLOP/s bf16, "
                     "1640 GB/s HBM, 32 GiB"}),
    (("v5p",), {
        "tier": "tpu-v5p", "flops_per_s": 459e12,
        "hbm_bytes_per_s": 2765e9, "hbm_bytes": 95 * 2**30,
        "peak_note": "TPU v5p datasheet: 459 TFLOP/s bf16, "
                     "2765 GB/s HBM, 95 GiB"}),
    (("v5e", "v5 lite", "v5litepod"), {
        "tier": "tpu-v5e", "flops_per_s": 197e12,
        "hbm_bytes_per_s": 819e9, "hbm_bytes": 16 * 2**30,
        "peak_note": "TPU v5e datasheet: 197 TFLOP/s bf16, "
                     "819 GB/s HBM, 16 GiB"}),
    (("v4",), {
        "tier": "tpu-v4", "flops_per_s": 275e12,
        "hbm_bytes_per_s": 1228e9, "hbm_bytes": 32 * 2**30,
        "peak_note": "TPU v4 datasheet: 275 TFLOP/s bf16, "
                     "1228 GB/s HBM, 32 GiB"}),
)

_CPU_TIER: Dict[str, Any] = {
    "tier": "cpu-placeholder", "flops_per_s": 1e11,
    "hbm_bytes_per_s": 5e10, "hbm_bytes": 0,
    "placeholder": True,
    "peak_note": "CPU placeholder tier (~100 GFLOP/s, ~50 GB/s): an "
                 "order-of-magnitude stand-in, NOT a measured host peak "
                 "— roofline fractions on CPU are only comparable "
                 "within one machine and one run",
}


_UNKNOWN_TIER: Dict[str, Any] = {
    "tier": "unknown", "flops_per_s": 0.0,
    "hbm_bytes_per_s": 0.0, "hbm_bytes": 0,
    "peak_note": "device not in the peak table: achieved rates are "
                 "reported, roofline fractions are not",
}


def peak_for(device_kind: str, platform: str = "") -> Dict[str, Any]:
    """Peak-throughput tier for a jax ``device_kind`` string: the CPU
    platform gets the flagged placeholder tier, an accelerator matches
    the datasheet table by substring or gets the peak-less "unknown"
    tier."""
    if platform.lower() == "cpu":
        return dict(_CPU_TIER)
    kind = (device_kind or "").lower()
    for needles, tier in _PEAK_TIERS:
        if any(n in kind for n in needles):
            return dict(tier)
    return dict(_UNKNOWN_TIER)


def _local_device_tier() -> Dict[str, Any]:
    try:
        import jax

        d = jax.devices()[0]
        n = len(jax.devices())
    except Exception as exc:
        tier = dict(_UNKNOWN_TIER)
        tier.update({"device_kind": "", "platform": "", "device_count": 0,
                     "error": f"{type(exc).__name__}: {exc}"[:200]})
        return tier
    tier = peak_for(d.device_kind, d.platform)
    tier.update({"device_kind": d.device_kind, "platform": d.platform,
                 "device_count": n})
    return tier


# -- cost rows ----------------------------------------------------------------

# catalog key: (group, bucket, variant, quant, kernels, mesh)
Key = Tuple[str, int, str, str, str, str]


@dataclass
class ProgramCost:
    """The XLA cost model's view of ONE compiled program variant.  When
    the same key recompiles at a new padded shape (shape autotuning),
    the newest capture wins — the catalog describes what is serving NOW,
    history belongs to the runtimestats compile counters."""

    group: str
    bucket: int
    variant: str
    quant: str = "off"
    kernels: str = "off"
    mesh: str = "off"
    measured_variant: str = ""
    shape: Tuple[int, ...] = ()
    flops: float = 0.0
    bytes_accessed: float = 0.0
    transcendentals: float = 0.0
    argument_bytes: int = 0
    output_bytes: int = 0
    temp_bytes: int = 0
    hbm_peak_bytes: int = 0
    generated_code_bytes: int = 0
    capture_s: float = 0.0
    captured_unix: float = 0.0
    error: str = ""

    def key(self) -> Key:
        return (self.group, self.bucket, self.variant, self.quant,
                self.kernels, self.mesh)

    def snapshot(self) -> Dict[str, Any]:
        out = {
            "group": self.group, "bucket": self.bucket,
            "variant": self.variant, "quant": self.quant,
            "kernels": self.kernels, "mesh": self.mesh,
            "shape": list(self.shape),
            "flops": self.flops,
            "bytes_accessed": self.bytes_accessed,
            "hbm_peak_bytes": self.hbm_peak_bytes,
            "argument_bytes": self.argument_bytes,
            "output_bytes": self.output_bytes,
            "temp_bytes": self.temp_bytes,
            "capture_s": round(self.capture_s, 6),
        }
        if self.transcendentals:
            out["transcendentals"] = self.transcendentals
        if self.error:
            out["error"] = self.error
        return out


def _first_dict(obj: Any) -> Dict[str, Any]:
    # jax's compiled.cost_analysis() has returned both a bare dict and a
    # [dict] across versions; normalize without caring which era we're in
    if isinstance(obj, (list, tuple)):
        obj = obj[0] if obj else {}
    return obj if isinstance(obj, dict) else {}


class ProgramCatalog:
    """Deferred-capture catalog of every live compiled program, bound to
    one metrics registry (same single-binding discipline as
    RuntimeStats).  Hot path cost: ``note_compile`` stores a lower thunk
    + abstract shapes under one short lock; the AOT compile only runs at
    read time via :meth:`capture_pending`."""

    def __init__(self, registry=None, max_programs: int = 512) -> None:
        if registry is None:
            from .metrics import default_registry

            registry = default_registry
        self.registry = registry
        self.enabled = True
        self.max_programs = max_programs
        self._lock = threading.Lock()
        self._pending: Dict[Key, Tuple[Callable[[], Any], ProgramCost]] = {}
        self._costs: Dict[Key, ProgramCost] = {}
        self._capture_errors = 0
        self._noted = 0
        # armed by bootstrap when observability.programstats.slo_capture
        # is enabled; /debug/programs reports its capture ring
        self.slo_capture: Optional["SLOCaptureController"] = None

        self.flops_gauge = registry.gauge(
            "llm_program_flops",
            "XLA cost-model FLOPs per compiled program variant "
            "(group/bucket/variant/quant/kernels/mesh)")
        self.bytes_gauge = registry.gauge(
            "llm_program_bytes",
            "XLA cost-model bytes accessed per compiled program variant")
        self.hbm_gauge = registry.gauge(
            "llm_program_hbm_peak_bytes",
            "Compiled-program HBM footprint (argument + output + temp "
            "buffers) from XLA memory_analysis()")
        self.roofline_gauge = registry.gauge(
            "llm_program_roofline_fraction",
            "Achieved FLOP/s over the roofline-attainable peak "
            "min(peak_flops, intensity * peak_bw) for the device tier; "
            "CPU-tier fractions use a placeholder peak (see "
            "/debug/programs peak_note)")

    # -- capture -----------------------------------------------------------

    def note_compile(self, group: str, bucket: int, variant: str,
                     shape: Tuple[int, ...],
                     lower: Callable[[], Any], *,
                     measured_variant: str = "",
                     quant: str = "off", kernels: str = "off",
                     mesh: str = "off") -> None:
        """Register a freshly-compiled program for deferred cost capture.
        ``lower`` is a zero-arg thunk returning ``jit(f).lower(*abstract)``
        — built from ShapeDtypeStruct trees so it pins no device arrays.
        Bounded: past ``max_programs`` live keys, new notes are dropped
        (the census is similarly bounded by shape/bucket discipline)."""
        if not self.enabled:
            return
        cost = ProgramCost(
            group=group, bucket=int(bucket), variant=variant,
            quant=quant or "off", kernels=kernels or "off",
            mesh=mesh or "off",
            measured_variant=measured_variant or variant,
            shape=tuple(int(s) for s in shape))
        key = cost.key()
        with self._lock:
            if key not in self._costs and key not in self._pending \
                    and len(self._costs) + len(self._pending) \
                    >= self.max_programs:
                return
            # a re-compile of a known key (new padded shape) supersedes
            # the old capture: drop the stale cost row so the catalog
            # re-captures against the program actually serving
            self._costs.pop(key, None)
            self._pending[key] = (lower, cost)
            self._noted += 1

    def capture_pending(self, limit: Optional[int] = None) -> int:
        """Run the deferred AOT captures: ``lower().compile()`` +
        ``cost_analysis()`` + ``memory_analysis()`` per pending program.
        Each failure is recorded on its row (fail-open) — a CPU backend
        or jax version without one of the analyses still yields a row."""
        if not self.enabled:
            return 0
        with self._lock:
            keys = list(self._pending.keys())
        if limit is not None:
            keys = keys[:limit]
        done = 0
        for key in keys:
            with self._lock:
                entry = self._pending.pop(key, None)
            if entry is None:
                continue
            lower, cost = entry
            t0 = time.perf_counter()
            try:
                compiled = lower().compile()
                ca = _first_dict(compiled.cost_analysis())
                cost.flops = float(ca.get("flops", 0.0))
                cost.bytes_accessed = float(ca.get("bytes accessed", 0.0))
                cost.transcendentals = float(ca.get("transcendentals", 0.0))
                try:
                    ma = compiled.memory_analysis()
                except Exception:
                    ma = None
                if ma is not None:
                    cost.argument_bytes = int(getattr(
                        ma, "argument_size_in_bytes", 0) or 0)
                    cost.output_bytes = int(getattr(
                        ma, "output_size_in_bytes", 0) or 0)
                    cost.temp_bytes = int(getattr(
                        ma, "temp_size_in_bytes", 0) or 0)
                    cost.generated_code_bytes = int(getattr(
                        ma, "generated_code_size_in_bytes", 0) or 0)
                    cost.hbm_peak_bytes = (cost.argument_bytes
                                           + cost.output_bytes
                                           + cost.temp_bytes)
                else:
                    cost.error = "memory_analysis unavailable"
            except Exception as exc:  # capture must never break reads
                cost.error = f"{type(exc).__name__}: {exc}"[:200]
                with self._lock:
                    self._capture_errors += 1
            cost.capture_s = time.perf_counter() - t0
            cost.captured_unix = time.time()
            with self._lock:
                self._costs[key] = cost
            done += 1
        return done

    # -- retirement --------------------------------------------------------

    def retire(self, group: Optional[str] = None,
               variant_prefix: Optional[str] = None) -> int:
        """Drop cost rows (and their gauge samples) for programs a hot
        flip just rebuilt — the census purge's catalog twin.  Matches by
        exact ``group`` and/or census-variant prefix (``"packed:"``
        retires every packed program across groups)."""
        with self._lock:
            keys = [k for k in list(self._costs) + list(self._pending)
                    if (group is None or k[0] == group)
                    and (variant_prefix is None
                         or k[2].startswith(variant_prefix))]
            rows = [self._costs.pop(k, None) for k in keys]
            for k in keys:
                self._pending.pop(k, None)
        for cost in rows:
            if cost is not None:
                self._remove_gauges(cost)
        return len(keys)

    def _labels(self, cost: ProgramCost) -> Dict[str, str]:
        return {"group": cost.group, "bucket": str(cost.bucket),
                "variant": cost.variant, "quant": cost.quant,
                "kernels": cost.kernels, "mesh": cost.mesh}

    def _remove_gauges(self, cost: ProgramCost) -> None:
        labels = self._labels(cost)
        for g in (self.flops_gauge, self.bytes_gauge, self.hbm_gauge,
                  self.roofline_gauge):
            try:
                g.remove(**labels)
            except Exception:
                pass

    # -- reading -----------------------------------------------------------

    def rows(self) -> List[ProgramCost]:
        with self._lock:
            return [self._costs[k] for k in sorted(self._costs)]

    def catalog(self, runtime_stats=None, capture: bool = True
                ) -> Dict[str, Any]:
        """The joined observatory read: cost-model rows x runtimestats
        warm EWMAs -> achieved FLOP/s, bytes/s and roofline fraction
        against the device-tier peaks.  Publishes the llm_program_*
        gauges as a side effect (same scrape-refresh discipline as
        RuntimeStats.report)."""
        if capture:
            self.capture_pending()
        tier = _local_device_tier()
        peak_flops = float(tier.get("flops_per_s") or 0.0)
        peak_bw = float(tier.get("hbm_bytes_per_s") or 0.0)

        measured: Dict[Tuple[str, int, str], Dict[str, Any]] = {}
        if runtime_stats is not None:
            try:
                for m in runtime_stats.programs():
                    measured[(m["group"], m["bucket"], m["variant"])] = m
            except Exception:
                pass

        rows: List[Dict[str, Any]] = []
        for cost in self.rows():
            row = cost.snapshot()
            labels = self._labels(cost)
            self.flops_gauge.set(cost.flops, **labels)
            self.bytes_gauge.set(cost.bytes_accessed, **labels)
            self.hbm_gauge.set(float(cost.hbm_peak_bytes), **labels)
            m = measured.get((cost.group, cost.bucket,
                              cost.measured_variant))
            if m is not None:
                row["measured_variant"] = cost.measured_variant
                row["executes"] = m.get("executes", 0)
                row["execute_ewma_s"] = m.get("execute_ewma_s", 0.0)
                fill = m.get("token_fill_ratio",
                             m.get("fill_ratio_mean", 0.0))
                row["token_fill_ratio"] = fill
                ewma = float(m.get("execute_ewma_s") or 0.0)
                if ewma > 0.0 and cost.flops > 0.0:
                    achieved = cost.flops / ewma
                    row["achieved_flops_per_s"] = achieved
                    row["useful_flops_per_s"] = achieved * float(fill)
                    if cost.bytes_accessed > 0.0:
                        row["achieved_bytes_per_s"] = \
                            cost.bytes_accessed / ewma
                        intensity = cost.flops / cost.bytes_accessed
                        row["arithmetic_intensity"] = intensity
                        attainable = min(peak_flops, intensity * peak_bw) \
                            if peak_flops and peak_bw else 0.0
                        if attainable > 0.0:
                            frac = achieved / attainable
                            row["roofline_fraction"] = frac
                            row["bound"] = "compute" \
                                if intensity * peak_bw >= peak_flops \
                                else "memory"
                            self.roofline_gauge.set(frac, **labels)
            rows.append(row)

        with self._lock:
            pending = len(self._pending)
            errors = self._capture_errors
        out = {
            "enabled": self.enabled,
            "device": tier,
            "programs": rows,
            "catalog_size": len(rows),
            "pending_captures": pending,
            "capture_errors": errors,
        }
        if self.slo_capture is not None:
            out["slo_captures"] = self.slo_capture.links()
        return out

    def report(self, runtime_stats=None) -> Dict[str, Any]:
        """Operator snapshot for GET /debug/programs."""
        return self.catalog(runtime_stats=runtime_stats)

    def clear(self) -> None:
        for cost in self.rows():
            self._remove_gauges(cost)
        with self._lock:
            self._pending.clear()
            self._costs.clear()
            self._capture_errors = 0
            self._noted = 0


# -- SLO-burn-triggered capture ----------------------------------------------


class SLOCaptureController:
    """One bounded profiler trace + a program-catalog snapshot per firing
    SLO alert.  Subscribes to the runtime event bus; on
    ``slo_alert_firing`` (cooldown-gated so a flapping alert can't
    profile the process to death) it arms ProfilerControl for
    ``trace_s`` seconds and snapshots the catalog's roofline rows into a
    bounded ring, cross-linked from the flight recorder dump."""

    def __init__(self, catalog: Optional[ProgramCatalog] = None,
                 runtime_stats=None, profiler=None, flightrec=None,
                 events=None, trace_s: float = 2.0,
                 cooldown_s: float = 300.0, max_captures: int = 8) -> None:
        self.catalog = catalog
        self.runtime_stats = runtime_stats
        self.profiler = profiler
        self.flightrec = flightrec
        self.events = events
        self.trace_s = float(trace_s)
        self.cooldown_s = float(cooldown_s)
        self._captures: deque = deque(maxlen=max_captures)
        self._lock = threading.Lock()
        self._last_mono: float = 0.0
        self._seq = 0
        self._unsub: Optional[Callable[[], None]] = None
        self._stop_timer: Optional[threading.Timer] = None
        if flightrec is not None:
            # the dump-side cross-link: flight-recorder dumps carry the
            # capture ring so an incident bundle points at its traces
            try:
                flightrec.capture_provider = self.links
            except Exception:
                pass

    # -- wiring ------------------------------------------------------------

    def attach(self, bus) -> None:
        """Subscribe to the event bus (idempotent: re-attach replaces)."""
        self.detach()
        if bus is None:
            return
        try:
            unsub = bus.subscribe(self.on_event)
            self.events = bus
        except Exception:
            unsub = None
        with self._lock:
            self._unsub = unsub

    def detach(self) -> None:
        with self._lock:
            unsub, self._unsub = self._unsub, None
        if unsub is not None:
            try:
                unsub()
            except Exception:
                pass

    def on_event(self, ev) -> None:
        from ..runtime.events import SLO_ALERT_FIRING

        if getattr(ev, "stage", None) != SLO_ALERT_FIRING:
            return
        detail = getattr(ev, "detail", None) or {}
        self.trigger(objective=str(detail.get("objective", "")),
                     reason="slo_alert")

    # -- capture -----------------------------------------------------------

    def trigger(self, objective: str = "", reason: str = "manual"
                ) -> Optional[Dict[str, Any]]:
        """Run one capture now (cooldown permitting).  Returns the
        capture record, or None when suppressed by cooldown."""
        now = time.monotonic()
        with self._lock:
            if self._last_mono and now - self._last_mono < self.cooldown_s:
                return None
            self._last_mono = now
            self._seq += 1
            seq = self._seq
        cap: Dict[str, Any] = {
            "id": f"slocap-{seq}",
            "at_unix": time.time(),
            "objective": objective,
            "reason": reason,
            "trace_s": self.trace_s,
        }
        # program-catalog snapshot: the roofline rows AT the burn, not
        # minutes later when an operator gets paged
        if self.catalog is not None:
            try:
                snap = self.catalog.catalog(
                    runtime_stats=self.runtime_stats)
                cap["catalog_size"] = snap.get("catalog_size", 0)
                cap["programs"] = snap.get("programs", [])[:64]
                cap["device"] = snap.get("device", {})
            except Exception as exc:
                cap["catalog_error"] = str(exc)[:200]
        # one bounded profiler trace; a trace already running (operator-
        # started, or a previous burn) is respected, never clobbered
        if self.profiler is not None and self.trace_s > 0.0:
            try:
                started = self.profiler.start()
            except Exception as exc:
                started = {"started": False, "error": str(exc)[:200]}
            if started.get("started"):
                cap["trace_dir"] = started.get("dir", "")
                timer = threading.Timer(self.trace_s, self._stop_trace)
                timer.daemon = True
                timer.name = "slo-capture-stop"
                with self._lock:
                    self._stop_timer = timer
                timer.start()
            else:
                cap["trace_skipped"] = started.get(
                    "error", "profiler busy")
        self._captures.append(cap)
        if self.events is not None:
            try:
                from ..runtime.events import SLO_CAPTURE

                self.events.emit(
                    SLO_CAPTURE, id=cap["id"], objective=objective,
                    trace_dir=cap.get("trace_dir", ""),
                    catalog_size=cap.get("catalog_size", 0))
            except Exception:
                pass
        return cap

    def _stop_trace(self) -> None:
        try:
            if self.profiler is not None:
                self.profiler.stop()
        except Exception:
            pass

    def join(self, timeout: float = 5.0) -> None:
        """Wait for an in-flight bounded trace to stop (tests + orderly
        shutdown: the stop timer must not outlive the process teardown)."""
        with self._lock:
            timer = self._stop_timer
        if timer is not None:
            timer.join(timeout)

    # -- reading -----------------------------------------------------------

    def links(self) -> List[Dict[str, Any]]:
        """Cross-link rows for the flight recorder: capture id, time,
        objective, trace dir — enough to find the full snapshot in
        /debug/programs and the trace on disk."""
        return [{"id": c["id"], "at_unix": c["at_unix"],
                 "objective": c.get("objective", ""),
                 "reason": c.get("reason", ""),
                 "trace_dir": c.get("trace_dir", ""),
                 "catalog_size": c.get("catalog_size", 0)}
                for c in list(self._captures)]

    def report(self) -> List[Dict[str, Any]]:
        return [dict(c) for c in self._captures]


# process-global default (single-engine/dev posture, same pattern as
# runtimestats.default_runtime_stats)
default_program_stats = ProgramCatalog()

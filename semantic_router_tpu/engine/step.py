"""One device step of the engine, written once for every runner.

The runners of engine.classify (per task, fused trunk group, generation)
differ in what they stack, which program they call and how they cut an
item's answer out of what came back.  What a step IS does not differ, and
stands here: the ``engine.step`` annotation with its stage annotations
(observability.batchtrace, the one instrument on the profiler's clock),
the shape census and compile detection under one key, the program-cost
catalogue, the host clock around dispatch + readback with its one
``record_step`` sample, the forward counters, and ``finish()`` on every
exit — a raising program included, because failing batches are exactly
the ones traces must explain.  ``InferenceEngine._run_batch`` shows the
order: open, ``stage("stack")``, ``stage("h2d")``, ``program()``,
``stage("dispatch")``, ``stage("readback")``, ``ran()``, ``stage("demux")``.

The program call itself stays in the runner's frame: JAX takes the
traceback with every operation it traces, so a frame more between the
entry point and a jitted call makes its first call slower (PERF.md
section 6, PR 27).
"""

from __future__ import annotations

import time
from typing import Any, Dict, Optional, Sequence

# scope -> the ``path`` label of llm_trunk_forwards_total (a generation's
# forwards are counted by runtimestats.record_generation instead)
_FORWARD_PATH = {"task": "traditional", "trunk": "fused"}


class EngineStep:
    """``scope`` and ``name`` make the group every reader keys on
    (``task:<name>`` | ``trunk:<gid>`` | ``gen:<task>``); ``variant`` is
    the measured series of ``record_step`` (``split`` | ``fused`` |
    ``fused_mesh`` | ``packed`` | ``packed_mesh`` | ``gen.<stage>``) and
    ``census`` the program's key in the compile census (the variant
    unless one variant holds several programs).  ``items`` are the batch
    items whose request traces ride this step; ``rows`` the real rows it
    carries when that is not their count.  ``packed``, ``mesh`` and
    ``kernels`` say which of the fused trunk's step counters it feeds;
    ``span_attrs`` go on a traced step's ``batch.execute`` span; further
    keywords are integer facts of the ``engine.step`` annotation."""

    def __init__(self, engine, items: Sequence[Any], *, scope: str,
                 name: str, bucket: int, padded_rows: int, kind: str,
                 flavour: str, variant: str, census: Optional[str] = None,
                 rows: Optional[int] = None, tokens_real: int = 0,
                 tokens_padded: int = 0, segments: int = 0,
                 meta: Optional[Dict[str, Any]] = None,
                 packed: bool = False, mesh: bool = False,
                 kernels: Sequence[str] = (),
                 span_attrs: Optional[Dict[str, Any]] = None,
                 **facts: int) -> None:
        from ..observability import batchtrace

        self.engine = engine
        self.scope, self.name = scope, name
        self.group = f"{scope}:{name}"
        self.bucket, self.padded_rows = int(bucket), int(padded_rows)
        self.rows = len(items) if rows is None else int(rows)
        self.variant, self.census = variant, census or variant
        self.tokens_real, self.tokens_padded = tokens_real, tokens_padded
        self.segments = segments
        self.meta, self.packed, self.mesh = meta, packed, mesh
        self.kernels = kernels
        self.fresh = False
        self._t0 = 0.0
        # opened BEFORE host stacking, so that a request's batch.wait span
        # ends where its queue wait ends: stacking and H2D belong to the
        # step, not to phantom queue congestion
        self._trace = batchtrace.start_step(
            items, group=self.group, bucket=bucket,
            max_batch=engine.cfg.max_batch_size, padded_rows=padded_rows,
            kind=kind, flavour=flavour, rows=self.rows,
            tokens_real=tokens_real, **facts)
        self.stage = self._trace.stage
        if span_attrs and self._trace.traced:
            self._trace.attrs.update(span_attrs)

    def __enter__(self) -> "EngineStep":
        return self

    def __exit__(self, *exc) -> None:
        self.finish()

    def program(self, fn=None, args: tuple = (),
                kwargs: Optional[Dict[str, Any]] = None) -> None:
        """The program this step is about to run, with its device
        arguments: a fresh (group, census key, shape) is one XLA compile,
        which the sample accounts apart and the cost catalogue registers
        (a generation's forwards name no ``fn``: nothing to register).
        The host clock starts here."""
        eng = self.engine
        shape = (self.padded_rows, self.bucket)
        eng._note_shape(self.group, shape)
        self.fresh = eng._step_fresh(self.group, self.census, shape)
        if self.fresh and fn is not None:
            eng._capture_program(self.group, self.bucket, self.census,
                                 shape, fn, args, self.variant, self.meta,
                                 kwargs)
        self._t0 = time.perf_counter()

    def ran(self) -> None:
        """The program's answer is on the host: one always-on step sample
        (observability.runtimestats: a bounded deque append; fused steps
        carry token fill and segments, the series the packing auto-tuner
        consumes) and the forward counters.  Never raises."""
        seconds = time.perf_counter() - self._t0
        eng = self.engine
        try:
            eng._runtime_stats.record_step(
                self.group, self.bucket, self.variant, self.rows,
                self.padded_rows, seconds, compiled=self.fresh,
                tokens_real=self.tokens_real,
                tokens_padded=self.tokens_padded, segments=self.segments)
        except Exception:
            pass
        path = _FORWARD_PATH.get(self.scope)
        if path is None:
            return
        m = eng._series()
        m.trunk_forwards.inc(group=self.name, path=path)
        # a packed step IS a fused trunk forward (dashboards sum
        # path="fused" for bank coalescing); packing has its own counter
        if self.packed:
            m.packed_steps.inc(group=self.name)
        if self.mesh:
            m.mesh_steps.inc(group=self.name)
        # llm_engine_kernel_steps_total: the operator's proof that a tuned
        # kernel is on the hot path, not just accepted by config
        for kernel in self.kernels:
            m.kernel_steps.inc(group=self.name, kernel=kernel)

    def finish(self) -> None:
        """End the step on both clocks (idempotent)."""
        self._trace.finish()

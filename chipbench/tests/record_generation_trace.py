"""How ``data/generation_<loop>.xplane.pb`` were made (run it again after a
change to what the program writes around a generation):

    JAX_PLATFORMS=cpu python -m chipbench.tests.record_generation_trace

For each loop (``greedy``: ``GreedyGenerator``; ``blockdiff``:
``BlockDiffusionGenerator``) a toy model behind a real ``InferenceEngine``,
two callers in a closed loop with a request trace each, two requests a
caller, under a real profiler session on the CPU: two generations of two
rows.  The program's own annotations are kept as recorded, times and stats
(``engine.*``); everything else of the host plane goes.  A CPU has no
device plane, so one is BUILT from the recorded steps: a step's program
runs from the middle of its ``dispatch`` stage to three quarters of its
``readback`` stage, as ONE op under one ``XLA Modules`` event whose
``run_id`` a ``DoEnqueueProgram`` event at its first instant and a
``CompleteCallbacks`` event at its last carry (the two clocks agree) — a
device that is busy whenever a program runs, so that all of its idle time
lies between programs.  ``generation_blockdiff_cut.xplane.pb`` is the block loop's file
without the third ``engine.gen.turn`` of its first generation,
``generation_greedy_skewed.xplane.pb`` the greedy loop's with the device's
clock 1.5 ms behind the host's.  Needs
tensorflow's ``xplane_pb2`` (the tests that read the files do not).
"""

from __future__ import annotations

import glob
import os
import tempfile
import threading

HERE = os.path.dirname(os.path.abspath(__file__))
DEVICE = "/device:TPU:0"
KEEP = ("engine.",)


def _toy(loop: str):
    from tests import test_step_instrument as toys

    return {"greedy": toys._greedy_loop, "blockdiff": toys._block_loop}[loop]()


def record(loop: str, log_dir: str) -> str:
    import jax

    from semantic_router_tpu.config.schema import InferenceEngineConfig
    from semantic_router_tpu.engine.classify import InferenceEngine
    from semantic_router_tpu.observability.tracing import Tracer

    gen, new_tokens, _ = _toy(loop)
    eng = InferenceEngine(InferenceEngineConfig(
        max_batch_size=2, max_wait_ms=50.0, seq_len_buckets=[32]))
    eng.register_generative("guard", gen)
    tracer = Tracer(sample_rate=0.0)
    words = ["alpha beta", "gamma delta epsilon"]

    def caller(i: int, rounds: int) -> None:
        for r in range(rounds):
            with tracer.span("router.route"):
                eng.generate("guard", [f"{words[i]} {r}"],
                             max_new_tokens=new_tokens)

    def both(rounds: int) -> None:
        threads = [threading.Thread(target=caller, args=(i, rounds))
                   for i in range(2)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(120.0)

    try:
        both(1)  # compiles the two-row programs
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(log_dir, profiler_options=opts)
        try:
            both(2)
        finally:
            jax.profiler.stop_trace()
    finally:
        eng.shutdown()
    (path,) = glob.glob(os.path.join(log_dir, "plugins", "profile", "*",
                                     "*.xplane.pb"))
    return path


def rebuild(path: str, drop_turn: int = -1, device_behind_ps: int = 0):
    """The recorded file with the host plane cut to the program's own
    annotations and a device plane built from its steps, its clock
    ``device_behind_ps`` behind the host's."""
    from tensorflow.tsl.profiler.protobuf import xplane_pb2

    space = xplane_pb2.XSpace()
    with open(path, "rb") as f:
        space.ParseFromString(f.read())
    (host,) = [p for p in space.planes if p.name == "/host:CPU"]
    out = xplane_pb2.XSpace()
    new = out.planes.add()
    new.id, new.name = host.id, host.name
    names = {i: m.name for i, m in host.event_metadata.items()}
    kept = {i for i, n in names.items() if n.startswith(KEEP)}
    for i in kept:
        new.event_metadata[i].CopyFrom(host.event_metadata[i])
    for i, m in host.stat_metadata.items():
        new.stat_metadata[i].CopyFrom(m)
    run_id_stat = max(host.stat_metadata) + 1
    new.stat_metadata[run_id_stat].id = run_id_stat
    new.stat_metadata[run_id_stat].name = "run_id"
    enqueue = max(host.event_metadata) + 1
    new.event_metadata[enqueue].id = enqueue
    new.event_metadata[enqueue].name = "DoEnqueueProgram"
    complete = enqueue + 1
    new.event_metadata[complete].id = complete
    new.event_metadata[complete].name = "CompleteCallbacks"

    runs = []  # (start_ps, end_ps) on the session's clock, by run id
    turns = sorted(line.timestamp_ns * 1000 + e.offset_ps
                   for line in host.lines for e in line.events
                   if names[e.metadata_id] == "engine.gen.turn")
    dropped = turns[drop_turn] if drop_turn >= 0 else None
    for line in host.lines:
        events = [e for e in line.events if e.metadata_id in kept]
        if not events:
            continue
        nl = new.lines.add()
        nl.id, nl.name, nl.timestamp_ns = line.id, line.name, \
            line.timestamp_ns
        base = line.timestamp_ns * 1000
        stages = {}
        for e in sorted(events, key=lambda e: e.offset_ps):
            name = names[e.metadata_id]
            if name == "engine.gen.turn" and base + e.offset_ps == dropped:
                continue
            nl.events.add().CopyFrom(e)
            if name == "engine.step.dispatch":
                stages["dispatch"] = e
            elif name == "engine.step.readback":
                d = stages.pop("dispatch")
                mid = d.offset_ps + d.duration_ps // 2
                runs.append((base + mid,
                             base + e.offset_ps + e.duration_ps * 3 // 4))
                # the host enqueues at the program's first instant and
                # learns of its end at its last: the two clocks agree
                for meta, at, length in (
                        (enqueue, mid, d.duration_ps // 4),
                        (complete, runs[-1][1] - base, 1000)):
                    call = nl.events.add()
                    call.metadata_id = meta
                    call.offset_ps, call.duration_ps = at, length
                    st = call.stats.add()
                    st.metadata_id, st.uint64_value = run_id_stat, len(runs)

    dev = out.planes.add()
    dev.id, dev.name = 1, DEVICE
    dev.stat_metadata[1].id, dev.stat_metadata[1].name = 1, "run_id"
    dev.event_metadata[1].id, dev.event_metadata[1].name = 1, "jit_program(1)"
    dev.event_metadata[2].id = 2
    dev.event_metadata[2].name = "%fusion.1 = f32[2,32]{1,0} fusion()"
    runs = [(s - device_behind_ps, t - device_behind_ps) for s, t in runs]
    t0 = min(s for s, _ in runs) // 1000  # ns
    modules, ops = dev.lines.add(), dev.lines.add()
    modules.id, modules.name, modules.timestamp_ns = 1, "XLA Modules", t0
    ops.id, ops.name, ops.timestamp_ns = 2, "XLA Ops", t0
    for s, t, run_id in sorted(
            (s, t, i) for i, (s, t) in enumerate(runs, 1)):
        for line, meta in ((modules, 1), (ops, 2)):
            e = line.events.add()
            e.metadata_id = meta
            e.offset_ps, e.duration_ps = s - t0 * 1000, t - s
        st = modules.events[-1].stats.add()
        st.metadata_id, st.uint64_value = 1, run_id
    return out


def main() -> None:
    for loop in ("greedy", "blockdiff"):
        with tempfile.TemporaryDirectory() as tmp:
            path = record(loop, tmp)
            variants = {"": {}, "_cut": {"drop_turn": 2}} \
                if loop == "blockdiff" else \
                {"": {}, "_skewed": {"device_behind_ps": 1_500_000_000}}
            for suffix, how in variants.items():
                target = os.path.join(
                    HERE, "data", f"generation_{loop}{suffix}.xplane.pb")
                with open(target, "wb") as f:
                    f.write(rebuild(path, **how).SerializeToString())
                print(f"wrote {target} ({os.path.getsize(target)} bytes)")


if __name__ == "__main__":
    main()

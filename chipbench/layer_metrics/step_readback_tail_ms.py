"""Per step, from the end of its program's last device op to the end of
``engine.step.readback``: transfer and conversion alone, without the wait for
the program that the host-clock ``engine_step_ms`` includes.  Mean, ms."""

from chipbench.layer_metrics import _program_spans


def read(run):
    ps = _program_spans.load(run)
    if ps is None:
        return None
    tails = [st.stages["readback"][1] - st.device[1] for st in ps.steps
             if st.device is not None and "readback" in st.stages]
    return sum(tails) / len(tails) * 1e3 if tails else None

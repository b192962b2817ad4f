"""A decode step's length less its own program's time on the device
(first to last op of the run the step launched): what the host adds to
every token, mean over the traced ``gen.decode`` steps whose run is found
on the device, ms."""

from chipbench.layer_metrics import _ar_spans, _gen_spans


def read(run):
    gaps = [(st.end - st.start) - (st.device[1] - st.device[0])
            for st in _gen_spans.steps(run) or ()
            if st.facts.get("flavour") == _ar_spans.DECODE
            and st.device is not None]
    return sum(gaps) / len(gaps) * 1e3 if gaps else None

"""Block forwards (denoise and commit, without prefill) per committed
block over the window, from the step counters (``record_step`` samples of
group ``gen:<task>`` by their variant): 2 to ``denoising_steps`` + 1; 5.0
while no confidence crosses the threshold."""

from chipbench.layer_metrics._window import step_delta


def _of(variant):
    return lambda key: key[0].startswith("gen:") and key[2] == variant


def read(run):
    commits = step_delta(run["steps"], _of("gen.commit"))["executes"]
    denoise = step_delta(run["steps"], _of("gen.denoise"))["executes"]
    return (denoise + commits) / commits if commits else None

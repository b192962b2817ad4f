"""The matrix states and conv windows of the linear layers as a share of
all cache bytes of the traced prefills, % (``cache_bytes_state +
cache_bytes_conv`` over those and ``cache_bytes_full`` of their
``engine.gen.forward`` markers): what three layers in four cost in cache."""

from chipbench.layer_metrics import _lin_spans


def read(run):
    return _lin_spans.state_cache_share(run)

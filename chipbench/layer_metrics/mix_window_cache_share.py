"""The sliding layers' rings as a share of all cache bytes of the traced
LONG prefills, % (``cache_bytes_window`` over ``cache_bytes_window +
cache_bytes_full`` of their ``engine.gen.forward`` markers): what a cache
allocator by layer type would hand back."""

from chipbench.layer_metrics import _mix_spans


def read(run):
    return _mix_spans.window_cache_share(run)

"""Keys the decode forwards' queries selected over the keys visible to them
(``keys_selected`` / ``keys_visible`` of the ``engine.gen.forward`` markers
of flavour ``gen.decode``, summed on the device over rows and full layers):
under 1 the selection is at work."""

from chipbench.layer_metrics import _ar_spans, _sa_spans


def read(run):
    return _sa_spans.selected_share(run, _ar_spans.DECODE)

"""Tokens a decode step committed per live row (``committed_tokens`` /
``drafted`` of the traced ``gen.decode`` markers): between 1 (no draft
accepted) and 2; under 1 + the acceptance rate by what the last step of a
row drops when its budget ends inside a pair."""

from chipbench.layer_metrics import _mtp_spans


def read(run):
    return _mtp_spans.ratio(run, "committed_tokens", "drafted")

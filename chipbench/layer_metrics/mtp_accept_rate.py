"""Drafts that were the model's own choice over drafts verified
(``accepted`` / ``drafted`` of the ``engine.gen.forward`` markers of the
traced ``gen.decode`` steps; one draft a live row and step): the drafter's
acceptance rate."""

from chipbench.layer_metrics import _mtp_spans


def read(run):
    return _mtp_spans.ratio(run, "accepted", "drafted")

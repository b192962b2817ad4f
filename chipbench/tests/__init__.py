"""The harness's own checks (run by hand: ``python -m pytest chipbench/tests``)."""

"""Test bootstrap: the tests ALWAYS run on a virtual 8-device CPU
platform, so every sharding/pjit test runs without TPU hardware and a
machine that has a chip never lends it to the suite.  The env var is
overwritten (not setdefault) and the config pinned before any backend
initializes.  On-chip behaviour is chip_smoke.py's job, and
tests/test_tpu_compile.py hands the kernels to the TPU compiler for a
described (not attached) v5e.
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import jax

jax.config.update("jax_platforms", "cpu")

import pathlib

import pytest

# -- analysis mode (docs/ANALYSIS.md) ---------------------------------------
#
# VSR_ANALYZE=1 (always-on for the smoke suites via their Makefile
# targets, opt-in elsewhere) arms two session-level gates:
#
#   * the runtime lock-order witness: threading.Lock/RLock constructed
#     from repo code record acquisition-order edges during the run; at
#     session end the edges merge with the static lock graph
#     (analysis/locks.py) and any cycle fails the session;
#   * the thread-leak gate: the session must end with no new
#     non-daemon threads and no unexpected daemon threads;
#   * the ACCESS witness (the race detector's runtime half,
#     docs/ANALYSIS.md): the hot concurrent classes get a sampled
#     __setattr__ recorder tagging each write with (thread, locks
#     held); at session end every empty-lockset pair across >=2
#     threads merges with the static lockset pass
#     (analysis/races.py) on relpath:line sites and fails the
#     session unless baseline-justified.
#
# The witness is installed AFTER the jax import above: jax's internal
# locks predate it (and out-of-repo constructions get raw primitives
# back anyway), so tier-1 overhead stays <5% on the smoke suites; the
# access watch samples 1/8 writes (VSR_ACCESS_SAMPLE) for the same
# bound.

VSR_ANALYZE = os.environ.get("VSR_ANALYZE", "") not in ("", "0")

# Intentionally process-lifetime threads (beyond the witness defaults).
# Every entry needs a reason — this list is the thread-leak baseline.
THREAD_ALLOWLIST = (
    # jax CPU client callback/dispatch threads live for the process
    r"^jax",
    # stdlib concurrent.futures pools joined at interpreter exit
    r"^ThreadPoolExecutor-",
)

_thread_baseline = None

if VSR_ANALYZE:
    from semantic_router_tpu.analysis import witness as _witness

    _witness.install()


def pytest_sessionstart(session):
    global _thread_baseline
    if VSR_ANALYZE:
        _thread_baseline = _witness.thread_snapshot()
        _witness.arm_access_watch()


def pytest_runtest_setup(item):
    # re-arm at each test boundary: watch-list modules imported since
    # the last check get wrapped now (sys.modules lookups only — a
    # session that never imports the engine never pays its import)
    if VSR_ANALYZE:
        _witness.arm_access_watch()


def pytest_sessionfinish(session, exitstatus):
    if not VSR_ANALYZE:
        return
    from semantic_router_tpu.analysis import (
        BASELINE_PATH,
        load_baseline,
        static_lock_edges,
    )
    from semantic_router_tpu.analysis.findings import apply_baseline
    from semantic_router_tpu.analysis.witness import (
        DEFAULT_THREAD_ALLOWLIST,
    )

    from semantic_router_tpu.analysis import races as _races

    problems = _witness.check_lock_order(static_lock_edges())
    problems += _witness.check_thread_leaks(
        _thread_baseline or set(),
        allowlist=tuple(DEFAULT_THREAD_ALLOWLIST) + THREAD_ALLOWLIST)
    # the race detector's cross-proof: runtime empty-lockset pairs
    # merge with the static lockset findings on relpath:line sites —
    # a pair landing on a statically-flagged write adopts the static
    # key, so ONE baseline entry governs both halves
    access = _witness.check_access_races()
    if access:
        import semantic_router_tpu.analysis as _an

        static_races = _races.check(
            os.path.join(_an.REPO_ROOT, "semantic_router_tpu"),
            rel_root=_an.REPO_ROOT)
        problems += _races.merge_runtime(static_races, access)
    # honor baseline.toml here too: a justified suppression must mean
    # the same thing to `make analyze` and to this session gate (stale-
    # entry hygiene is `make analyze`'s job, not the smoke suites')
    try:
        sup = [s for s in load_baseline(BASELINE_PATH)
               if s.checker in ("locks", "thread-leak", "races")]
        problems = apply_baseline(problems, sup).findings
    except ValueError:
        pass  # malformed baseline fails `make analyze` with the detail
    if problems:
        print("\n=== VSR_ANALYZE session gates FAILED ===")
        for f in problems:
            print(f.render())
        print(f"({len(_witness.runtime_edges())} runtime lock edges "
              f"recorded this session)")
        session.exitstatus = 1


FIXTURES = pathlib.Path(__file__).parent / "fixtures"


@pytest.fixture(scope="session")
def fixture_config_path() -> str:
    return str(FIXTURES / "router_config.yaml")


@pytest.fixture(scope="session")
def router_config(fixture_config_path):
    from semantic_router_tpu.config import load_config

    return load_config(fixture_config_path)


@pytest.fixture()
def seen(monkeypatch):
    """Every annotation the program writes on the profiler's clock, as
    (name, facts), whether or not a session runs."""
    from semantic_router_tpu.observability import batchtrace

    seen = []
    real = batchtrace.trace_span

    def spy(name, **facts):
        seen.append((name, facts))
        return real(name, **facts)

    monkeypatch.setattr(batchtrace, "trace_span", spy)
    return seen

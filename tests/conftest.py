"""Test configuration: run everything on a virtual 8-device CPU mesh.

Mirrors the reference's CGO split strategy (``SURVEY.md`` §4: GStreamer/cgo
code is re-tested against stubs with CGO_ENABLED=0): libtpu-dependent Pallas
kernels run in interpret mode on CPU; multi-chip sharding is validated on
XLA's host-platform device simulator, exactly how the driver's
``dryrun_multichip`` does it.
"""

import os

# Must be set before jax initialises its backends.  FORCE cpu: tests never
# touch an accelerator (a chip belongs to one process at a time, and the
# suite runs in several).  The files that compile for a described TPU,
# tests/test_tpu_compile*.py, do so from fixtures and still on this backend.
os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()
os.environ.setdefault("JAX_ENABLE_X64", "0")

import jax  # noqa: E402

# config.update OUTRANKS the env var above, so pin that too (same pin
# ``__graft_entry__.dryrun_multichip`` applies), then verify it took
# (config.update silently no-ops once backends are initialised) and force
# deterministic early CPU init.
jax.config.update("jax_platforms", "cpu")
assert jax.default_backend() == "cpu", (
    f"jax backend is {jax.default_backend()!r}, not cpu — backends were "
    "initialised before conftest could pin jax_platforms"
)

jax.config.update("jax_default_matmul_precision", "highest")
# CPU tests keep NO persistent compile cache (the one cache rule lives in
# ``helix_tpu.device.compile_cache`` and is for the programs, not the suite).

import threading  # noqa: E402

import pytest  # noqa: E402

# Watchdog backstop for a test that stalls: pytest's own faulthandler
# plugin (``faulthandler_timeout`` in pytest.ini) dumps tracebacks if a
# test phase stalls; this timer then hard-exits so CI
# never hangs forever. The timer spans one test's whole runtest protocol
# (setup+call+teardown); the grace above faulthandler_timeout absorbs that
# plus cold XLA compiles. Longest legitimate test (32k-token chunked
# prefill e2e) runs ~90-120 s cold. Set HELIX_TEST_TIMEOUT_S=0 to disable.


def _parse_timeout(default: float = 480.0) -> float:
    try:
        return float(os.environ.get("HELIX_TEST_TIMEOUT_S", default))
    except ValueError:
        return default


_TEST_TIMEOUT_S = _parse_timeout()


def _hard_exit(item) -> None:
    try:
        # restore the real stderr fd so the message reaches the terminal
        # (we are about to _exit; thread-safety of capman no longer matters)
        capman = item.config.pluginmanager.get_plugin("capturemanager")
        if capman is not None:
            capman.suspend_global_capture(in_=True)
    except Exception:  # noqa: BLE001 — best effort on the way out
        pass
    try:
        os.write(
            2,
            (
                f"\n[conftest watchdog] test {item.nodeid!r} ran longer "
                f"than {_TEST_TIMEOUT_S:.0f}s (setup+call+teardown) — hard "
                f"exit. A faulthandler dump appears above iff one phase "
                f"alone exceeded faulthandler_timeout.\n"
            ).encode(),
        )
    except OSError:
        pass
    os._exit(2)


@pytest.hookimpl(hookwrapper=True)
def pytest_runtest_protocol(item, nextitem):
    timer = None
    if _TEST_TIMEOUT_S > 0:
        timer = threading.Timer(_TEST_TIMEOUT_S, _hard_exit, args=(item,))
        timer.daemon = True
        timer.start()
    yield
    if timer is not None:
        timer.cancel()


# Under ``--dist loadfile`` a file is one worker's, and xdist hands the files
# out by their NUMBER of tests, most first: a file of few, long tests would
# start last and the run would wait on it with the other workers idle (two
# such files side by side in that tail, each compile 2.7 cores wide, cost
# PR 44's first hand-in its run).  These are handed out FIRST: whole steps
# compiled for the described chip (eight minutes in one process) and the
# vision towers; the rest follow in xdist's own order.  With them
# ``test_observability.py``, which needs a YOUNG worker: behind the delta-rule
# family file and six more in one process its first request to an in-process
# runner gets no answer in its 30 s (alone: 3 s; cause not found, ROADMAP
# Queue 3, 13).
_FEW_AND_LONG = (
    "test_tpu_compile_steps.py", "test_qwen2_vl.py", "test_engine_vl.py",
    "test_observability.py",
)


def pytest_configure(config):
    # the scheduler would sort the files by their number of tests again
    if hasattr(config.option, "loadscopereorder"):
        config.option.loadscopereorder = False


def pytest_collection_modifyitems(items):
    import collections

    rank = {name: i for i, name in enumerate(_FEW_AND_LONG)}
    size = collections.Counter(item.path for item in items)
    # (stable: a file's tests stay together and in their order)
    items.sort(key=lambda item: (
        rank.get(item.path.name, len(rank)), -size[item.path]))


@pytest.fixture(autouse=True, scope="module")
def _bound_xla_state():
    """Clear jax's executable/tracing caches after every test module.

    With all 537 tests in one process, XLA:CPU eventually segfaults inside
    backend_compile (observed r5, deterministic at ~93% of the suite, in a
    compile that passes when the file runs alone — accumulated-state
    crash in this jax build, sibling of the AOT-cache segfault above).
    Bounding live compiled-executable state per module avoids it; the
    cost is cross-module recompiles, which only shared-model helper
    modules pay."""
    yield
    jax.clear_caches()


@pytest.fixture(scope="session")
def cpu_devices():
    devs = jax.devices()
    assert len(devs) == 8, f"expected 8 virtual devices, got {len(devs)}"
    return devs


@pytest.fixture()
def rng():
    return jax.random.PRNGKey(0)


# ---------------------------------------------------------------------------
# Per-test duration recording: every run (tier-1 included, which passes
# -p no:cacheprovider so pytest's own cache is unavailable) appends each
# test's setup+call+teardown seconds to .pytest_last_durations.json in the
# repo root.  ``tools/slowest_tests.py`` prints the top offenders — the
# wall-clock-creep watchdog for keeping tier-1 under its timeout.
# ---------------------------------------------------------------------------

_DURATIONS: dict = {}


@pytest.hookimpl
def pytest_runtest_logreport(report):
    if report.when in ("setup", "call", "teardown"):
        _DURATIONS[report.nodeid] = (
            _DURATIONS.get(report.nodeid, 0.0) + report.duration
        )


@pytest.hookimpl
def pytest_sessionfinish(session):
    if not _DURATIONS:
        return
    import json

    path = os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        ".pytest_last_durations.json",
    )
    try:
        with open(path, "w") as f:
            json.dump(
                {
                    "total_seconds": round(sum(_DURATIONS.values()), 3),
                    "tests": {
                        k: round(v, 4) for k, v in _DURATIONS.items()
                    },
                },
                f,
            )
    except OSError:
        pass  # read-only checkout: recording is best-effort

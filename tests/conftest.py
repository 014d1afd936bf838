"""Test configuration: force an 8-device virtual CPU mesh before JAX imports.

Multi-chip sharding paths are validated on a virtual CPU mesh
(xla_force_host_platform_device_count=8); real-TPU benchmarking happens in
benchmark/run.py, not in the test suite.
"""

from tieredstorage_tpu.utils.platforms import pin_virtual_cpu

pin_virtual_cpu(8)

import importlib.util  # noqa: E402

import pytest  # noqa: E402

#: Optional third-party packages: the library degrades gracefully without
#: them (lazy imports raise ModuleNotFoundError only on the paths that need
#: them), and the suite must degrade the same way — skip, not fail.
OPTIONAL_DEPENDENCIES = ("cryptography", "zstandard")
HAVE_CRYPTOGRAPHY = importlib.util.find_spec("cryptography") is not None
HAVE_ZSTANDARD = importlib.util.find_spec("zstandard") is not None


def _optional_dep_missing(exc):
    """Walk the cause chain for a ModuleNotFoundError naming an optional
    dependency (the library wraps them, e.g. RemoteStorageException from a
    failed copy whose transform needed zstd)."""
    seen = set()
    while exc is not None and id(exc) not in seen:
        seen.add(id(exc))
        if isinstance(exc, ModuleNotFoundError) and any(
            dep in str(exc) for dep in OPTIONAL_DEPENDENCIES
        ):
            return exc
        exc = exc.__cause__ or exc.__context__
    return None


@pytest.hookimpl(hookwrapper=True)
def pytest_runtest_makereport(item, call):
    outcome = yield
    report = outcome.get_result()
    if report.failed and call.excinfo is not None:
        missing = _optional_dep_missing(call.excinfo.value)
        if missing is not None:
            report.outcome = "skipped"
            report.longrepr = (
                str(item.fspath), item.location[1],
                f"skipped: optional dependency missing: {missing}",
            )


def pytest_sessionfinish(session, exitstatus):
    """LockWitness + RaceWitness gates (`make chaos` runs with
    TSTPU_LOCK_WITNESS=1): any lock-acquisition-order violation observed
    during the whole session — including inside daemons and pool threads no
    single test asserts on — fails the run, validating the static
    lock-order checker's DAG against real executions; and every sampled
    shared-attribute mutation must have held its statically inferred guard
    (or be a declared single-thread/unguarded site), validating the
    guarded-by race inference the same way."""
    from tieredstorage_tpu.utils.locks import witness, witness_enabled

    if not witness_enabled():
        return
    violations = witness().violations
    if violations:
        print("\nLockWitness: lock-order violations observed:", flush=True)
        for v in violations:
            print(f"  {v}", flush=True)
        session.exitstatus = 1
    else:
        print(
            f"\nLockWitness: DAG held ({len(witness().edges())} distinct "
            "acquisition-order edges observed, 0 violations)",
            flush=True,
        )

    from tieredstorage_tpu.analysis import races

    crosscheck = races.runtime_crosscheck()
    if crosscheck["violations"]:
        print("\nRaceWitness: guarded-by cross-check violations:", flush=True)
        for v in crosscheck["violations"]:
            print(f"  {v}", flush=True)
        session.exitstatus = 1
    else:
        print(
            f"RaceWitness: {len(crosscheck['validated'])} site(s) validated "
            f"against the static inference, 0 violations "
            f"({len(crosscheck['unobserved'])} inferred guard(s) not "
            "exercised this session)",
            flush=True,
        )


@pytest.fixture
def tmp_storage_root(tmp_path):
    root = tmp_path / "storage-root"
    root.mkdir()
    return root

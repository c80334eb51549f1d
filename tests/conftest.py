"""Shared pytest config for the tier-1 suite.

The ``slow`` marker (declared in pytest.ini) carves out the fast tier that
CI runs on every push (``scripts/ci_fast.sh`` / ``-m "not slow"``).  Slow
standalone tests carry an explicit ``@pytest.mark.slow``; for the
arch-parametrized model tests the heavyweight configs are marked here so
the parametrize decorators stay readable.

The suite runs on the CPU backend.  XLA's host platform is split into four
virtual devices (set before any test initializes a backend) so that the
real executor's multi-device schedules have a device per lane; the
executor refuses a schedule that names more devices than are visible.
"""
import os

import pytest

_xla_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _xla_flags:
    os.environ["XLA_FLAGS"] = (
        f"{_xla_flags} --xla_force_host_platform_device_count=4".strip())

# Reduced configs that still take many seconds per test to jit on CPU.
_SLOW_ARCHS = ("seamless_m4t_medium", "gemma3_12b")


def pytest_collection_modifyitems(items):
    for item in items:
        if (item.fspath.basename == "test_models.py"
                and any(f"[{a}]" in item.name for a in _SLOW_ARCHS)):
            item.add_marker(pytest.mark.slow)


@pytest.fixture(autouse=True)
def _verify_memory_accounting(monkeypatch):
    """Reconcile the MemoryManager's residency ledger at every ``sync``.

    ``MemoryManager.verify()`` cross-checks logical residency (array
    location bits, tier membership) against the pool ledger; running it at
    each quiescent point turns silent accounting drift anywhere in the fast
    suite into an immediate failure at the sync that caused it, instead of
    a bogus eviction three scenarios later.  Sim-only: the real executor's
    worker threads may still be installing physical values when ``sync``
    observes the logical state mid-test teardown."""
    from repro.core.scheduler import GrScheduler

    orig_sync = GrScheduler.sync

    def sync_and_verify(self, *a, **kw):
        out = orig_sync(self, *a, **kw)
        if type(self.executor).__name__ == "SimExecutor":
            report = self.memory.verify(raise_on_drift=False)
            assert report.ok, \
                f"memory accounting drift at sync: {report}"
        return out

    monkeypatch.setattr(GrScheduler, "sync", sync_and_verify)

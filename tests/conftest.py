"""Test harness: force an 8-virtual-device CPU platform.

Multi-chip logic is tested without TPU hardware via XLA's virtual host
devices (SURVEY.md §4) — the TPU answer to "multi-node tests without a
cluster". XLA_FLAGS must be set before the first backend init; the
platform is pinned with ``jax.config.update`` so a test run never reaches
for an accelerator whatever JAX_PLATFORMS says.
"""
import os

_flags = os.environ.get('XLA_FLAGS', '')
if '--xla_force_host_platform_device_count' not in _flags:
    os.environ['XLA_FLAGS'] = (
        _flags + ' --xla_force_host_platform_device_count=8').strip()
# No persistent compile cache in tests (here and in every child that
# inherits the environment): cli.main and the bench builders configure one
# (code2vec_tpu/compile_cache.py), a test must not depend on what an earlier
# run left in it, and XLA:CPU's AOT loader logs a machine-feature mismatch
# on every load (jaxlib 0.9.0).
os.environ['JAX_ENABLE_COMPILATION_CACHE'] = 'false'

import jax  # noqa: E402
import pytest  # noqa: E402

jax.config.update('jax_platforms', 'cpu')


@pytest.fixture
def pallas_interpret():
    """Run every Pallas kernel traced inside the test in the interpreter —
    the only way a forced TPU kernel runs on this CPU platform
    (ops/_pallas_common.py)."""
    from code2vec_tpu.ops._pallas_common import interpret_kernels
    with interpret_kernels():
        yield


def pytest_configure(config):
    # tier-1 runs with -m 'not slow' (ROADMAP.md); register the marker
    # so the opt-in heavy tests (e.g. the 50k-vector IVF recall
    # acceptance) don't warn as typos
    config.addinivalue_line(
        'markers', 'slow: heavy acceptance tests, excluded from tier-1')


# Tier-1 runtime-budget guard (ISSUE 17): the suite runs under a hard
# wall-clock cap (ROADMAP.md), and single tests creeping past ~20s are
# how the cap gets eaten one PR at a time.  Flag them loudly at the end
# of the run so the offender is moved behind @pytest.mark.slow (or
# shrunk) BEFORE the cap is at risk — a warning, not a failure, because
# CI machines vary.
TIER1_SINGLE_TEST_BUDGET_S = 20.0
_over_budget = []


@pytest.hookimpl(hookwrapper=True)
def pytest_runtest_makereport(item, call):
    outcome = yield
    report = outcome.get_result()
    if report.when != 'call':
        return
    if item.get_closest_marker('slow') is not None:
        return  # opted out of tier-1: its duration is its own business
    if report.duration > TIER1_SINGLE_TEST_BUDGET_S:
        _over_budget.append((item.nodeid, report.duration))


def pytest_terminal_summary(terminalreporter):
    if not _over_budget:
        return
    terminalreporter.section('tier-1 runtime budget')
    terminalreporter.write_line(
        'WARNING: %d test(s) exceeded the ~%.0fs single-test tier-1 '
        'budget — mark them @pytest.mark.slow or shrink them '
        '(tests/conftest.py):' % (len(_over_budget),
                                  TIER1_SINGLE_TEST_BUDGET_S))
    for nodeid, duration in sorted(_over_budget, key=lambda x: -x[1]):
        terminalreporter.write_line('  %7.1fs  %s' % (duration, nodeid))

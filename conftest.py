"""Root pytest hooks: the runtime lock sanitizer (repro-lint v2).

``REPRO_SANITIZE=1 pytest ...`` patches the lock factories BEFORE test
modules import repo code, so every repo lock — including module-level ones
like the telemetry tracer's id counter — is created through a recording
proxy.  At session end the witnessed acquisition graph is cross-checked
against the static LOCK edge model:

* dynamic lock-order inversions fail the run (exit 1);
* blocking-under-lock events fail the run unless the file has a LOCK001
  baseline entry (one suppression model for the static and dynamic gates);
* static edges never witnessed are reported as stale model debt
  (informational — dead path or coverage hole);
* confirmed edges are printed so the cross-validation is visible.

Without the env var the sanitizer stays off.

Tests, and the processes they spawn, run without JAX's persistent
compilation cache: entry points turn it on (``launch.compile_cache``), and
a test that drives one must not write a cache into the checkout.
"""
import os
import sys

os.environ["JAX_ENABLE_COMPILATION_CACHE"] = "false"

_ROOT = os.path.dirname(os.path.abspath(__file__))
_SRC = os.path.join(_ROOT, "src")
if _SRC not in sys.path:
    sys.path.insert(0, _SRC)

from repro.analysis import sanitizer  # noqa: E402  (needs src on sys.path)


def pytest_configure(config):
    sanitizer.install_from_env(_ROOT)


def pytest_sessionfinish(session, exitstatus):
    san = sanitizer.active()
    if san is None:
        return
    witness = san.witness
    # Restore the raw primitives before the heavyweight cross-check.
    san.uninstall()
    tr = session.config.get_terminal_writer() if hasattr(
        session.config, "get_terminal_writer") else None

    def emit(line):
        if tr is not None:
            tr.line(line)
        else:                                       # pragma: no cover
            print(line)

    emit(f"sanitizer: {witness.acquisitions} sanitized acquisitions, "
         f"{len(witness.edges)} witnessed edges")
    allowed = sanitizer.baseline_allowed_paths(
        os.path.join(_ROOT, "scripts", "lint_baseline.txt"))
    failed = False
    for v in witness.inversions:
        emit(v.render())
        failed = True
    for v in witness.blocking:
        if v.site.rsplit(":", 1)[0] in allowed:
            emit(f"(allowed by LOCK001 baseline) {v.render()}")
            continue
        emit(v.render())
        failed = True
    for line in sanitizer.cross_check(witness, _ROOT).render():
        emit(line)
    if failed:
        session.exitstatus = 1

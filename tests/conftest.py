"""Test-session bootstrap.

* Puts ``src/`` on sys.path so ``PYTHONPATH=src`` is not required when
  pytest is invoked from the repo root.
* Points the persistent schedule cache at a throwaway directory.
"""
import atexit
import os
import shutil
import sys
import tempfile

_SRC = os.path.join(os.path.dirname(__file__), "..", "src")
if os.path.isdir(_SRC) and os.path.abspath(_SRC) not in \
        [os.path.abspath(p) for p in sys.path]:
    sys.path.insert(0, os.path.abspath(_SRC))

# Hermetic persistent-schedule-cache: every fuse_* call in the suite
# reads/writes a throwaway directory, never the developer's
# ~/.cache/repro/schedules (stale entries there could mask search
# changes; test runs must not depend on machine state).  Tests that
# exercise the cache itself monkeypatch REPRO_CACHE_DIR per-test.
_SCHED_TMP = tempfile.mkdtemp(prefix="repro-sched-test-")
os.environ["REPRO_CACHE_DIR"] = _SCHED_TMP
atexit.register(shutil.rmtree, _SCHED_TMP, True)

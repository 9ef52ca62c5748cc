"""rtpulint static rules + runtime lock sanitizer.

Golden fixture snippets per rule — a seeded regression (positive), the
same snippet with an inline ``# rtpulint: disable=`` pragma (suppressed),
and an idiomatic clean variant — plus baseline multiset semantics, the
CLI exit-code contract, and the lock sanitizer's cycle / device-boundary
/ zero-overhead guarantees. Finally, the repo itself must lint clean
against the checked-in baseline (the same gate CI runs).
"""

import json
import os
import textwrap
import threading
import time

import pytest

from raphtory_tpu.analysis import (Baseline, Finding, LockSanitizer,
                                   analyze_module, analyze_project)
from raphtory_tpu.analysis import sanitizer as san_mod
from raphtory_tpu.analysis.cli import main as cli_main

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def rules_of(findings):
    return sorted({f.name for f in findings})


def lint(src: str, name: str = "mod.py"):
    return analyze_module(textwrap.dedent(src), name)


# ---------------------------------------------------------------------------
# RT001 env-not-in-cache-key


RT001_POSITIVE = """
    import functools
    import os

    @functools.lru_cache(maxsize=8)
    def compiled(n_pad):
        budget = int(os.environ.get("RTPU_TILE_BUDGET_MB", 256))
        return n_pad * budget
"""


def test_env_in_cached_body_flagged():
    fs = lint(RT001_POSITIVE)
    assert rules_of(fs) == ["env-not-in-cache-key"]
    assert "RTPU_TILE_BUDGET_MB" in fs[0].message
    assert "compiled" in fs[0].message


def test_env_via_module_helper_flagged():
    fs = lint("""
        import functools
        import os

        def _budget():
            return int(os.environ.get("RTPU_TILE_BUDGET_MB", 256))

        @functools.lru_cache(maxsize=8)
        def compiled(n_pad):
            return n_pad * _budget()
    """)
    assert "env-not-in-cache-key" in rules_of(fs)


def test_env_read_suppressed():
    fs = lint(RT001_POSITIVE.replace(
        "256))",
        "256))  # rtpulint: disable=env-not-in-cache-key"))
    assert fs == []


def test_env_tile_length_in_cached_factory_flagged():
    """The bug class RT001 exists for: an env-derived TILE LENGTH
    resolved inside an lru_cached kernel factory through a helper that
    falls back to a size rule — changing RTPU_TILE_BUDGET_MB mid-process
    would silently reuse programs tiled for the old budget (the failure
    of PR 2)."""
    fs = lint("""
        import functools
        import os

        def _edge_tile(m_pad):
            ov = os.environ.get("RTPU_TILE_BUDGET_MB")
            return int(ov) << 18 if ov else max(1, m_pad // 2048)

        @functools.lru_cache(maxsize=16)
        def compiled_tiled(n_pad, m_pad):
            tile = _edge_tile(m_pad)
            return (n_pad, m_pad, tile)
    """)
    assert "env-not-in-cache-key" in rules_of(fs)
    assert any("RTPU_TILE_BUDGET_MB" in f.message for f in fs)

    # the shipped idiom: the DISPATCH site resolves the knob and the
    # factory receives the resolved budget as a cache-key argument
    fs = lint("""
        import functools
        import os

        @functools.lru_cache(maxsize=16)
        def compiled_tiled(n_pad, m_pad, tile_budget):
            return (n_pad, m_pad, tile_budget)

        def dispatch(n_pad, m_pad, tiled):
            ov = os.environ.get("RTPU_TILE_BUDGET_MB", "256")
            budget = int(ov) << 20 if tiled else None
            return compiled_tiled(n_pad, m_pad, budget)
    """)
    assert fs == []


def test_env_threaded_as_cache_key_clean():
    fs = lint("""
        import functools
        import os

        @functools.lru_cache(maxsize=8)
        def compiled(n_pad, budget):
            return n_pad * budget

        def dispatch(n_pad):
            return compiled(n_pad,
                            int(os.environ.get("RTPU_TILE_BUDGET_MB", 256)))
    """)
    assert fs == []


# ---------------------------------------------------------------------------
# RT002 broad-except-retry


RT002_POSITIVE = """
    import time

    def fetch(do):
        for attempt in range(4):
            try:
                return do()
            except Exception:
                time.sleep(2 ** attempt)
"""


def test_broad_except_retry_flagged():
    fs = lint(RT002_POSITIVE)
    assert rules_of(fs) == ["broad-except-retry"]


def test_broad_except_retry_suppressed():
    fs = lint(RT002_POSITIVE.replace(
        "except Exception:",
        "except Exception:  # rtpulint: disable=RT002"))
    assert fs == []


def test_classified_retry_clean():
    # transfer-style: non-transient errors re-raise immediately
    fs = lint("""
        import time

        def fetch(do, transient):
            for attempt in range(4):
                try:
                    return do()
                except Exception as e:
                    if not transient(e):
                        raise
                    time.sleep(2 ** attempt)
    """)
    assert fs == []


def test_broad_except_outside_retry_loop_clean():
    # a tick guard with no backoff loop is a different idiom, not RT002
    fs = lint("""
        def tick(fn):
            try:
                fn()
            except Exception:
                pass
    """)
    assert fs == []


def test_policy_retry_loop_is_blessed_idiom():
    # the RT002 message now points at resilience/policy.RetryPolicy.run —
    # its own loop shape (classify → fatal raise, exhausted raise,
    # deadline raise, else sleep) must itself lint clean, or the blessed
    # idiom would flag itself
    fs = lint("""
        import time

        def run(fn, classify, attempts, backoff_s):
            err = None
            for attempt in range(1, attempts + 1):
                try:
                    return fn()
                except Exception as e:
                    if not classify(e):
                        raise
                    err = e
                    if attempt >= attempts:
                        raise
                    time.sleep(backoff_s(attempt))
    """)
    assert fs == []


def test_rt002_message_names_policy_module():
    fs = lint(RT002_POSITIVE)
    assert "resilience/policy.RetryPolicy.run" in fs[0].message


# ---------------------------------------------------------------------------
# RT003 host-sync-in-trace


RT003_POSITIVE = """
    import jax
    import numpy as np

    def factory():
        def run(x):
            y = np.asarray(x)
            return y.sum(), x.item()
        return jax.jit(run)
"""


def test_host_sync_in_trace_flagged():
    fs = lint(RT003_POSITIVE)
    assert rules_of(fs) == ["host-sync-in-trace"]
    assert len(fs) == 2   # np.asarray and .item()


def test_host_sync_float_on_traced_arg_flagged():
    fs = lint("""
        import jax

        @jax.jit
        def run(x):
            return float(x)
    """)
    assert rules_of(fs) == ["host-sync-in-trace"]


def test_host_sync_suppressed():
    fs = lint(RT003_POSITIVE.replace(
        "y = np.asarray(x)",
        "y = np.asarray(x)  # rtpulint: disable=host-sync-in-trace"
    ).replace(
        "return y.sum(), x.item()",
        "return y.sum(), x.item()  # rtpulint: disable=RT003"))
    assert fs == []


def test_same_named_method_not_traced():
    # regression: jax.jit(run) must resolve to the factory-local def, not
    # a method that happens to share the name (features.propagate bug)
    fs = lint("""
        import jax
        import numpy as np

        def factory():
            def run(x):
                return x + 1
            return jax.jit(run)

        class Engine:
            def run(self, x):
                return np.asarray(x).item()
    """)
    assert fs == []


# ---------------------------------------------------------------------------
# RT004 use-after-donate


RT004_POSITIVE = """
    import jax

    def step(state, delta):
        apply = jax.jit(lambda a, b: a + b, donate_argnums=(0,))
        out = apply(state, delta)
        return out + state
"""


def test_use_after_donate_flagged():
    fs = lint(RT004_POSITIVE)
    assert rules_of(fs) == ["use-after-donate"]
    assert "state" in fs[0].message


def test_use_after_donate_via_factory_flagged():
    # the repo idiom: an lru_cached factory returns jit(..., donate_argnums)
    fs = lint("""
        import functools
        import jax

        @functools.lru_cache(maxsize=8)
        def compiled():
            def apply(a, b):
                return a + b
            return jax.jit(apply, donate_argnums=(0,))

        def step(state, delta):
            fn = compiled()
            out = fn(state, delta)
            return out + state
    """)
    assert "use-after-donate" in rules_of(fs)


def test_use_after_donate_via_instrumented_factory_flagged():
    # PR 6 idiom: the factory wraps the donating jit in the ledger's
    # instrument() — the wrapper dispatches through, so donation (and
    # this rule) must see through it
    fs = lint("""
        import functools
        import jax
        from raphtory_tpu.obs import ledger as _ledger

        @functools.lru_cache(maxsize=8)
        def compiled():
            def apply(a, b):
                return a + b
            return _ledger.instrument(
                "k", jax.jit(apply, donate_argnums=(0,)))

        def step(state, delta):
            fn = compiled()
            out = fn(state, delta)
            return out + state
    """)
    assert "use-after-donate" in rules_of(fs)


def test_use_after_donate_suppressed():
    fs = lint(RT004_POSITIVE.replace(
        "return out + state",
        "return out + state  # rtpulint: disable=use-after-donate"))
    assert fs == []


def test_rebound_after_donate_clean():
    fs = lint("""
        import jax

        def step(state, delta):
            apply = jax.jit(lambda a, b: a + b, donate_argnums=(0,))
            state = apply(state, delta)
            return state + 1
    """)
    assert fs == []


# ---------------------------------------------------------------------------
# RT005 nondeterminism-in-trace


RT005_POSITIVE = """
    import time
    import jax

    def factory():
        def run(x):
            return x + time.time()
        return jax.jit(run)
"""


def test_nondeterminism_in_trace_flagged():
    fs = lint(RT005_POSITIVE)
    assert rules_of(fs) == ["nondeterminism-in-trace"]


def test_nondeterminism_suppressed():
    fs = lint(RT005_POSITIVE.replace(
        "return x + time.time()",
        "return x + time.time()  # rtpulint: disable=RT005"))
    assert fs == []


def test_clock_outside_trace_clean():
    fs = lint("""
        import time
        import jax

        def factory():
            t0 = time.time()
            def run(x):
                return x + t0
            return jax.jit(run)
    """)
    assert fs == []


# ---------------------------------------------------------------------------
# RT006 unguarded-module-state


RT006_POSITIVE = """
    _CACHE = {}

    def remember(key, value):
        _CACHE[key] = value
"""


def test_unguarded_module_state_flagged():
    fs = lint(RT006_POSITIVE)
    assert rules_of(fs) == ["unguarded-module-state"]
    assert "_CACHE" in fs[0].message


def test_unguarded_module_state_suppressed():
    fs = lint(RT006_POSITIVE.replace(
        "_CACHE[key] = value",
        "_CACHE[key] = value  # rtpulint: disable=unguarded-module-state"))
    assert fs == []


def test_locked_module_state_clean():
    fs = lint("""
        import threading

        _CACHE = {}
        _LOCK = threading.Lock()

        def remember(key, value):
            with _LOCK:
                _CACHE[key] = value
    """)
    assert fs == []


def test_local_shadow_clean():
    fs = lint("""
        _CACHE = {}

        def build(key, value):
            _CACHE = {}
            _CACHE[key] = value
            return _CACHE
    """)
    assert fs == []


# ---------------------------------------------------------------------------
# RT007 undocumented-knob (project-level)


def test_undocumented_knob_flagged_and_documented_clean():
    src = textwrap.dedent("""
        import os

        DEPTH = int(os.environ.get("RTPU_TEST_KNOB", 2))
    """)
    fs = analyze_project([("m.py", src)], docs_text="nothing here",
                         docs_name="docs/OPERATIONS.md")
    assert rules_of(fs) == ["undocumented-knob"]
    assert "RTPU_TEST_KNOB" in fs[0].message

    fs = analyze_project([("m.py", src)],
                         docs_text="| `RTPU_TEST_KNOB` | 2 | depth |",
                         docs_name="docs/OPERATIONS.md")
    assert fs == []


def test_undocumented_knob_suppressed():
    src = textwrap.dedent("""
        import os

        DEPTH = os.environ.get("RTPU_TEST_KNOB")  # rtpulint: disable=RT007
    """)
    fs = analyze_project([("m.py", src)], docs_text="")
    assert fs == []


# ---------------------------------------------------------------------------
# RT008 unused-import


def test_unused_import_flagged():
    fs = lint("""
        import os
        import sys

        print(sys.argv)
    """)
    assert rules_of(fs) == ["unused-import"]
    assert "'os'" in fs[0].message


def test_unused_import_suppressed():
    fs = lint("""
        import os  # rtpulint: disable=unused-import
        import sys

        print(sys.argv)
    """)
    assert fs == []


def test_dunder_all_reexport_clean():
    fs = lint("""
        from collections import deque

        __all__ = ["deque"]
    """)
    assert fs == []


def test_init_py_skipped():
    fs = lint("from collections import deque\n", name="pkg/__init__.py")
    assert fs == []


# ---------------------------------------------------------------------------
# RT009 blocking-call-under-lock (interprocedural)


RT009_POSITIVE = """
    import threading
    import time

    _LOCK = threading.Lock()

    def refresh():
        with _LOCK:
            time.sleep(1.0)
"""


def test_blocking_under_lock_flagged():
    fs = lint(RT009_POSITIVE)
    assert rules_of(fs) == ["blocking-call-under-lock"]
    assert "time.sleep" in fs[0].message and "_LOCK" in fs[0].message


def test_blocking_under_lock_through_call_chain():
    # the lock is taken in the caller, the blocking call hides in a
    # helper — exactly what the per-module rules could not see
    fs = lint("""
        import threading
        import time

        _LOCK = threading.Lock()

        def _backoff():
            time.sleep(2.0)

        def refresh():
            with _LOCK:
                _backoff()
    """)
    assert "blocking-call-under-lock" in rules_of(fs)
    assert "refresh" in fs[0].message and "_backoff" in fs[0].message


def test_blocking_under_lock_cross_module():
    files = [
        ("pkg/locks.py", textwrap.dedent("""
            import threading

            _MU = threading.Lock()

            def guarded(fn):
                with _MU:
                    fn()

            def refresh():
                from .slowpath import pull
                with _MU:
                    pull()
        """)),
        ("pkg/slowpath.py", textwrap.dedent("""
            import time

            def pull():
                time.sleep(0.5)
        """)),
    ]
    fs = analyze_project(files)
    rt9 = [f for f in fs if f.rule == "RT009"]
    assert rt9 and rt9[0].path == "pkg/slowpath.py"
    assert "pkg.locks.refresh" in rt9[0].message


def test_blocking_under_lock_through_init_reexport():
    # review regression: relative imports inside __init__.py resolved one
    # package too high (the package's dotted name already IS the base for
    # level=1), silently dropping every chain routed through a package
    # re-export out of the call graph
    files = [
        ("pkg/__init__.py", textwrap.dedent("""
            import threading

            from .slowpath import pull

            _MU = threading.Lock()

            def refresh():
                with _MU:
                    pull()
        """)),
        ("pkg/slowpath.py", textwrap.dedent("""
            import time

            def pull():
                time.sleep(0.5)
        """)),
    ]
    fs = analyze_project(files)
    rt9 = [f for f in fs if f.rule == "RT009"]
    assert rt9 and rt9[0].path == "pkg/slowpath.py"
    assert "pkg.refresh" in rt9[0].message


def test_socket_io_under_lock_in_scrape_loop_flagged():
    # the /clusterz peer-scrape shape (obs/cluster.py): holding the
    # snapshot-cache lock across the HTTP fan-out serializes every
    # scraper behind the slowest peer's socket timeout
    fs = lint("""
        import threading
        import urllib.request

        _CACHE_LOCK = threading.Lock()
        _CACHE = {}

        def scrape(urls):
            with _CACHE_LOCK:
                for u in urls:
                    with urllib.request.urlopen(u, timeout=2.0) as r:
                        _CACHE[u] = r.read()
    """)
    assert rules_of(fs) == ["blocking-call-under-lock"]
    assert "urlopen" in fs[0].message and "_CACHE_LOCK" in fs[0].message


def test_socket_io_outside_lock_scrape_loop_clean():
    # the clean idiom obs/cluster.PeerScraper uses: the network fan-out
    # completes lock-free; the lock only ever guards dict ops
    fs = lint("""
        import threading
        import urllib.request

        _CACHE_LOCK = threading.Lock()
        _CACHE = {}

        def scrape(urls):
            fetched = {}
            for u in urls:
                with urllib.request.urlopen(u, timeout=2.0) as r:
                    fetched[u] = r.read()
            with _CACHE_LOCK:
                _CACHE.update(fetched)
    """)
    assert "blocking-call-under-lock" not in rules_of(fs)


def test_blocking_under_lock_suppressed():
    fs = lint(RT009_POSITIVE.replace(
        "time.sleep(1.0)",
        "time.sleep(1.0)  # rtpulint: disable=RT009"))
    assert fs == []


def test_blocking_outside_lock_clean():
    fs = lint("""
        import threading
        import time

        _LOCK = threading.Lock()

        def refresh():
            with _LOCK:
                x = 1
            time.sleep(x)
    """)
    assert fs == []


def test_device_put_under_lock_flagged_and_condition_wait_clean():
    fs = lint("""
        import threading
        import jax

        _LOCK = threading.Lock()

        def ship(a):
            with _LOCK:
                return jax.device_put(a)
    """)
    assert rules_of(fs) == ["blocking-call-under-lock"]
    # Condition.wait RELEASES the lock — never a blocking-under-lock
    fs = lint("""
        import threading

        _CV = threading.Condition()

        def fence(pred):
            with _CV:
                _CV.wait_for(pred, timeout=1.0)
    """)
    assert fs == []


# ---------------------------------------------------------------------------
# RT010 shared-state-without-common-lock (interprocedural)


RT010_POSITIVE = """
    from http.server import BaseHTTPRequestHandler

    _SHARED = None

    def shared_engine():
        global _SHARED
        if _SHARED is None:
            _SHARED = object()
        return _SHARED

    class Handler(BaseHTTPRequestHandler):
        def do_GET(self):
            shared_engine()
"""


def test_shared_state_lazy_singleton_flagged():
    fs = lint(RT010_POSITIVE)
    assert rules_of(fs) == ["shared-state-without-common-lock"]
    assert "_SHARED" in fs[0].message


def test_shared_state_suppressed():
    fs = lint(RT010_POSITIVE.replace(
        "            _SHARED = object()",
        "            _SHARED = object()  "
        "# rtpulint: disable=shared-state-without-common-lock"))
    assert fs == []


def test_shared_state_locked_clean():
    fs = lint("""
        import threading
        from http.server import BaseHTTPRequestHandler

        _SHARED = None
        _SHARED_LOCK = threading.Lock()

        def shared_engine():
            global _SHARED
            if _SHARED is None:
                with _SHARED_LOCK:
                    if _SHARED is None:
                        _SHARED = object()
            return _SHARED

        class Handler(BaseHTTPRequestHandler):
            def do_GET(self):
                shared_engine()
    """)
    assert fs == []


def test_shared_state_two_roots_different_locks_flagged():
    # both writers hold A lock — but not the SAME lock: the guarding
    # intersection is empty, which is the hazard RT006 cannot see
    fs = lint("""
        import threading

        _STATE = {}
        _LOCK_A = threading.Lock()
        _LOCK_B = threading.Lock()

        def writer_a():
            with _LOCK_A:
                _STATE["a"] = 1

        def writer_b():
            with _LOCK_B:
                _STATE["b"] = 2

        def serve():
            threading.Thread(target=writer_a).start()
            threading.Thread(target=writer_b).start()
    """)
    assert "shared-state-without-common-lock" in rules_of(fs)


def test_thread_confined_instance_state_clean():
    # each Job's results list is written only from that job's own
    # thread root — confinement, not sharing (the Job.results shape)
    fs = lint("""
        import threading

        class Job:
            def __init__(self):
                self.results = []

            def start(self):
                threading.Thread(target=self._run).start()

            def _run(self):
                self.results.append(1)
    """)
    assert fs == []


def test_instance_state_two_roots_flagged():
    fs = lint("""
        import threading
        from http.server import BaseHTTPRequestHandler

        class Table:
            def __init__(self):
                self.rows = {}

            def put(self, k, v):
                self.rows[k] = v

        class Handler(BaseHTTPRequestHandler):
            table: Table = None

            def do_GET(self):
                self.table.put("g", 1)

            def do_POST(self):
                self.table.put("p", 2)
    """)
    assert "shared-state-without-common-lock" in rules_of(fs)


# ---------------------------------------------------------------------------
# RT011 unbounded-growth-on-request-path (interprocedural)


RT011_POSITIVE = """
    import threading
    from http.server import BaseHTTPRequestHandler

    class Job:
        def __init__(self):
            self.results = []

        def start(self):
            threading.Thread(target=self._run).start()

        def _run(self):
            self.results.append({"x": 1})

    class Manager:
        def submit(self):
            job = Job()
            job.start()
            return job

    class Handler(BaseHTTPRequestHandler):
        manager: Manager = None

        def do_POST(self):
            self.manager.submit()
"""


def test_unbounded_results_on_request_path_flagged():
    fs = lint(RT011_POSITIVE)
    assert "unbounded-growth-on-request-path" in rules_of(fs)
    f = next(f for f in fs if f.rule == "RT011")
    assert "Job.results" in f.message and "do_POST" in f.message


def test_unbounded_growth_suppressed():
    fs = lint(RT011_POSITIVE.replace(
        '            self.results.append({"x": 1})',
        '            self.results.append({"x": 1})  '
        '# rtpulint: disable=RT011'))
    assert [f.rule for f in fs if f.rule == "RT011"] == []


def test_capped_results_clean():
    # a shrink site anywhere in the project bounds the container
    fs = lint(RT011_POSITIVE.replace(
        '            self.results.append({"x": 1})',
        '            self.results.append({"x": 1})\n'
        '            del self.results[:-10]'))
    assert [f.rule for f in fs if f.rule == "RT011"] == []


def test_bounded_ring_and_counter_cell_clean():
    fs = lint("""
        from collections import deque
        from http.server import BaseHTTPRequestHandler

        _RECENT: deque = deque(maxlen=64)
        _COUNTS = [0]

        def note(x):
            _RECENT.append(x)
            _COUNTS[0] += 1

        class Handler(BaseHTTPRequestHandler):
            def do_POST(self):
                note(1)
    """)
    assert [f.rule for f in fs if f.rule == "RT011"] == []


# ---------------------------------------------------------------------------
# interprocedural RT001 / RT003 / RT004 (cross-module)


def test_env_in_cache_key_cross_module():
    files = [
        ("pkg/helpers.py", textwrap.dedent("""
            import os

            def budget():
                return int(os.environ.get("RTPU_TILE_BUDGET_MB", 256))
        """)),
        ("pkg/factory.py", textwrap.dedent("""
            import functools
            from .helpers import budget

            @functools.lru_cache(maxsize=8)
            def compiled(n_pad):
                return n_pad * budget()
        """)),
    ]
    fs = analyze_project(files, docs_text="RTPU_TILE_BUDGET_MB")
    rt1 = [f for f in fs if f.rule == "RT001"]
    assert rt1 and rt1[0].path == "pkg/helpers.py"
    assert "compiled" in rt1[0].message and "via" in rt1[0].message
    # the dispatch-resolved idiom stays clean: the factory takes the
    # value as a cache-key argument, the helper is called elsewhere
    files_clean = [
        files[0],
        ("pkg/factory.py", textwrap.dedent("""
            import functools
            from .helpers import budget

            @functools.lru_cache(maxsize=8)
            def compiled(n_pad, b):
                return n_pad * b

            def dispatch(n_pad):
                return compiled(n_pad, budget())
        """)),
    ]
    fs = analyze_project(files_clean, docs_text="RTPU_TILE_BUDGET_MB")
    assert [f for f in fs if f.rule == "RT001"] == []


def test_host_sync_in_trace_cross_module():
    files = [
        ("pkg/mathutil.py", textwrap.dedent("""
            import numpy as np

            def center(x):
                return np.asarray(x) - np.asarray(x).mean()
        """)),
        ("pkg/kernels.py", textwrap.dedent("""
            import jax
            from .mathutil import center

            def factory():
                def run(x):
                    return center(x) + 1
                return jax.jit(run)
        """)),
    ]
    fs = analyze_project(files)
    rt3 = [f for f in fs if f.rule == "RT003"]
    assert rt3 and rt3[0].path == "pkg/mathutil.py"
    assert "run" in rt3[0].message


def test_use_after_donate_cross_module():
    files = [
        ("pkg/compiled.py", textwrap.dedent("""
            import functools
            import jax

            @functools.lru_cache(maxsize=8)
            def compiled_apply():
                def apply(a, b):
                    return a + b
                return jax.jit(apply, donate_argnums=(0,))
        """)),
        ("pkg/driver.py", textwrap.dedent("""
            from .compiled import compiled_apply

            def step(state, delta):
                fn = compiled_apply()
                out = fn(state, delta)
                return out + state
        """)),
    ]
    fs = analyze_project(files)
    rt4 = [f for f in fs if f.rule == "RT004"]
    assert rt4 and rt4[0].path == "pkg/driver.py"
    assert "state" in rt4[0].message


# ---------------------------------------------------------------------------
# baseline + CLI


def test_baseline_multiset_semantics():
    src = textwrap.dedent(RT002_POSITIVE)
    old = analyze_project([("m.py", src)])
    bl = Baseline.from_findings(old)
    # unchanged tree: nothing new
    new, accepted, stale = bl.split(analyze_project([("m.py", src)]))
    assert new == [] and len(accepted) == len(old) and stale == 0
    # a SECOND copy of the same hazard in another function is new even
    # though the line text matches (fingerprint includes the symbol)
    src2 = src + textwrap.dedent("""
        def fetch2(do):
            for attempt in range(4):
                try:
                    return do()
                except Exception:
                    time.sleep(2 ** attempt)
    """)
    new, accepted, stale = bl.split(analyze_project([("m.py", src2)]))
    assert len(new) == 1 and len(accepted) == len(old)


def test_fingerprint_survives_code_motion():
    f1 = Finding("RT002", "broad-except-retry", "m.py", 10, 1, "msg",
                 symbol="fetch", line_text="except Exception:")
    f2 = Finding("RT002", "broad-except-retry", "m.py", 99, 1, "msg",
                 symbol="fetch", line_text="  except Exception:  ")
    assert f1.fingerprint == f2.fingerprint


def test_cli_exit_codes_and_baseline_workflow(tmp_path, capsys):
    pkg = tmp_path / "pkg"
    pkg.mkdir()
    (pkg / "m.py").write_text(textwrap.dedent(RT002_POSITIVE))
    (tmp_path / "tools").mkdir()
    root = str(tmp_path)
    # violation, no baseline → exit 1, finding rendered
    assert cli_main([str(pkg), "--root", root]) == 1
    out = capsys.readouterr().out
    assert "RT002 broad-except-retry" in out
    # accept it → exit 0 afterwards
    assert cli_main([str(pkg), "--root", root, "--write-baseline"]) == 0
    assert cli_main([str(pkg), "--root", root]) == 0
    # a new violation on top of the baseline → exit 1 again, json report
    (pkg / "m2.py").write_text("import os\n")
    report_path = tmp_path / "report.json"
    assert cli_main([str(pkg), "--root", root, "--format", "json",
                     "--output", str(report_path)]) == 1
    report = json.loads(report_path.read_text())
    assert [f["rule"] for f in report["new"]] == ["RT008"]
    assert report["stale_baseline_entries"] == 0


def test_cli_rule_filter(tmp_path):
    pkg = tmp_path / "pkg"
    pkg.mkdir()
    (pkg / "m.py").write_text("import os\n" + textwrap.dedent(RT002_POSITIVE))
    assert cli_main([str(pkg), "--root", str(tmp_path), "--no-baseline",
                     "--rule", "unused-import"]) == 1
    assert cli_main([str(pkg), "--root", str(tmp_path), "--no-baseline",
                     "--rule", "use-after-donate"]) == 0
    assert cli_main([str(pkg), "--root", str(tmp_path),
                     "--rule", "no-such-rule"]) == 2


def test_parse_error_is_a_finding():
    fs = analyze_project([("bad.py", "def broken(:\n")])
    assert [f.rule for f in fs] == ["RT000"]


def test_parse_error_survives_rule_filter():
    # --rule must not silently drop the only signal a file was skipped
    fs = analyze_project([("bad.py", "def broken(:\n")],
                         rules={"RT008", "unused-import"})
    assert [f.rule for f in fs] == ["RT000"]


def test_parse_error_is_never_baselinable():
    fs = analyze_project([("bad.py", "def broken(:\n")])
    bl = Baseline.from_findings(fs)
    assert bl.entries == []   # write path drops it
    # and even a hand-edited baseline entry cannot launder one
    bl.counts[fs[0].fingerprint] += 1
    new, accepted, _ = bl.split(fs)
    assert [f.rule for f in new] == ["RT000"] and accepted == []


def test_cli_refuses_filtered_baseline_write(tmp_path, capsys):
    pkg = tmp_path / "pkg"
    pkg.mkdir()
    (tmp_path / "tools").mkdir()
    (pkg / "m.py").write_text("import os\n" + textwrap.dedent(RT002_POSITIVE))
    root = str(tmp_path)
    assert cli_main([str(pkg), "--root", root, "--write-baseline"]) == 0
    # a filtered rewrite would drop the accepted RT002 entry — refused
    assert cli_main([str(pkg), "--root", root, "--rule", "unused-import",
                     "--write-baseline"]) == 2
    assert "refusing" in capsys.readouterr().err
    assert cli_main([str(pkg), "--root", root]) == 0   # baseline intact


# ---------------------------------------------------------------------------
# --fix autofix (RT008), --fix-diff, --timings / --budget-seconds


FIXABLE = """\
import os
import sys
from collections import OrderedDict, deque  # rtpulint: disable=RT008

print(sys.argv)
"""


def test_fix_unused_imports_idempotent_and_pragma_respecting():
    from raphtory_tpu.analysis.fixes import fix_unused_imports

    fixed, n = fix_unused_imports(FIXABLE, "m.py")
    assert n == 1
    assert "import os" not in fixed
    assert "import sys" in fixed            # used import survives
    assert "OrderedDict, deque" in fixed    # pragma'd line untouched
    again, n2 = fix_unused_imports(fixed, "m.py")
    assert n2 == 0 and again == fixed       # idempotent


def test_fix_two_statements_on_one_line():
    # `import os; import sys` with only os unused: the two statements
    # share a line, so their edits must MERGE — review caught the naive
    # per-node version deleting the rebuilt survivor
    from raphtory_tpu.analysis.fixes import fix_unused_imports

    fixed, n = fix_unused_imports(
        "import os; import sys\n\nprint(sys.argv)\n", "m.py")
    assert n == 1
    assert "import sys" in fixed and "os" not in fixed
    assert lint(fixed) == []


def test_fix_preserves_trailing_comment():
    # a trailing comment may be a pragma for ANOTHER rule or a reviewer
    # note — the rebuild must carry it over
    from raphtory_tpu.analysis.fixes import fix_unused_imports

    fixed, n = fix_unused_imports(
        "from collections import OrderedDict, deque  # keep: order\n\n"
        "d = OrderedDict()\n", "m.py")
    assert n == 1
    assert "# keep: order" in fixed and "deque" not in fixed


def test_fix_partial_from_import():
    from raphtory_tpu.analysis.fixes import fix_unused_imports

    src = textwrap.dedent("""
        from collections import (
            OrderedDict,
            deque,
        )

        d = OrderedDict()
    """)
    fixed, n = fix_unused_imports(src, "m.py")
    assert n == 1
    assert "deque" not in fixed
    assert "from collections import OrderedDict" in fixed
    assert lint(fixed) == []   # re-scan clean = the fix IS the fix


def test_cli_fix_and_fix_diff(tmp_path, capsys):
    pkg = tmp_path / "pkg"
    pkg.mkdir()
    (tmp_path / "tools").mkdir()
    target = pkg / "m.py"
    target.write_text(FIXABLE)
    root = str(tmp_path)
    # --fix-diff: suggestion only, file untouched
    diff_path = tmp_path / "fix.patch"
    assert cli_main([str(pkg), "--root", root,
                     "--fix-diff", str(diff_path)]) == 1
    assert target.read_text() == FIXABLE
    diff = diff_path.read_text()
    assert "-import os" in diff and "+import" not in diff.replace(
        "+++", "")
    # --fix: applied in place, scan then exits clean
    assert cli_main([str(pkg), "--root", root, "--fix"]) == 0
    assert "import os" not in target.read_text()
    assert cli_main([str(pkg), "--root", root]) == 0


def test_cli_timings_and_budget(tmp_path, capsys):
    pkg = tmp_path / "pkg"
    pkg.mkdir()
    (tmp_path / "tools").mkdir()
    (pkg / "m.py").write_text("import sys\n\nprint(sys.argv)\n")
    root = str(tmp_path)
    assert cli_main([str(pkg), "--root", root, "--format", "json",
                     "--timings"]) == 0
    report = json.loads(capsys.readouterr().out)
    assert set(report["timings_seconds"]) >= {
        "RT001", "RT008", "RT009", "RT010", "RT011", "RT012", "RT013",
        "RT014", "RT015", "model"}
    assert report["analysis_seconds"] >= 0
    # an absurd budget trips the exit even with zero findings
    assert cli_main([str(pkg), "--root", root,
                     "--budget-seconds", "0"]) == 1


def test_walker_picks_up_shebang_scripts(tmp_path):
    from raphtory_tpu.analysis.cli import _iter_py_files

    tools = tmp_path / "tools"
    tools.mkdir()
    script = tools / "mytool"
    script.write_text("#!/usr/bin/env python3\nimport sys\n")
    (tools / "data.bin").write_bytes(b"\x00\x01")
    (tools / "notes.txt").write_text("not python")
    found = _iter_py_files([str(tools)])
    assert str(script) in found
    assert all(not f.endswith((".bin", ".txt")) for f in found)


# ---------------------------------------------------------------------------
# the repo itself must be clean against the checked-in baseline


def _repo_scan_inputs():
    """(files, docs_text) for the package PLUS tests/ and tools/ (the
    rtpulint v2 scan set), via the same walker the CLI uses — the test
    gates and the CI lint job must scan the identical file set."""
    from raphtory_tpu.analysis.cli import _iter_py_files, _load

    roots = [os.path.join(REPO, d)
             for d in ("raphtory_tpu", "tests", "tools")]
    files = [_load(p, REPO) for p in _iter_py_files(roots)]
    with open(os.path.join(REPO, "docs", "OPERATIONS.md")) as fh:
        docs = fh.read()
    return files, docs


def test_repo_lints_clean_against_baseline():
    files, docs = _repo_scan_inputs()
    findings = analyze_project(files, docs_text=docs)
    bl_path = os.path.join(REPO, "tools", "rtpulint_baseline.json")
    baseline = Baseline.load(bl_path)
    new, _, _ = baseline.split(findings)
    assert new == [], "new rtpulint findings:\n" + "\n".join(
        f.render() for f in new)


def test_undocumented_knob_rule_passes_without_baseline_help():
    # the knob table must be complete in its own right (ISSUE: "must pass
    # clean, not via baseline")
    files, docs = _repo_scan_inputs()
    fs = analyze_project(files, docs_text=docs, rules={"RT007"})
    assert fs == []


def test_knob_table_rows_and_code_agree_both_ways():
    """ROADMAP D4's count, as a rule: every ``RTPU_*`` name the package's
    source holds has a ROW of its own in docs/OPERATIONS.md (RT007 above
    accepts a mention anywhere in the text), and every row names a knob
    some code still reads — a row outliving its knob sends an operator
    to turn something that turns nothing."""
    import re

    knob = re.compile(r"RTPU_[A-Z0-9]+(?:_[A-Z0-9]+)*")

    def names_under(*roots):
        from raphtory_tpu.analysis.cli import _iter_py_files

        out = set()
        for path in _iter_py_files([os.path.join(REPO, r) for r in roots]):
            with open(path) as fh:
                out |= set(knob.findall(fh.read()))
        return out

    rows = set()
    with open(os.path.join(REPO, "docs", "OPERATIONS.md")) as fh:
        for line in fh:
            if line.startswith("| `RTPU_"):
                rows |= set(knob.findall(line.split("|")[1]))
    package = names_under("raphtory_tpu")
    assert package - rows == set(), "knobs without a row"
    # rows may also name the knobs of the drivers around the package
    drivers = names_under("tools", "tests")
    assert rows - package - drivers == set(), "rows without a reader"


DOCUMENTS = ["README.md", ".claude/skills/verify/SKILL.md"] + sorted(
    "docs/" + f for f in os.listdir(os.path.join(REPO, "docs"))
    if f.endswith(".md"))


@pytest.mark.parametrize("document", DOCUMENTS)
def test_every_path_a_document_names_exists(document):
    """The knob-table rule, for paths: every back-quoted ``*.py``,
    ``*.md``, ``*.json``, ``*.yml`` or ``*.cpp`` path of a document
    resolves from the root, the package, ``docs/``, ``tests/``, ``tools/``
    or ``benchmark/`` (a bare module name: one file of that name in the
    package) — a path outliving its file sends a reader to code that is
    gone. Fenced blocks (a user's own files in an example command) and
    absolute paths (outside the checkout) are not the repo's to hold.
    PERF.md, ROADMAP.md and CHANGES.md are history and are not scanned."""
    import glob
    import re

    path = re.compile(r"(?<![\w./*<>{}$-])([\w.*/-]+\.(?:py|md|json|yml|cpp))"
                      r"(?![\w/*-])")
    roots = ("", "raphtory_tpu", "docs", "tests", "tools", "benchmark")

    def resolves(p):
        if any(glob.glob(os.path.join(REPO, r, p)) for r in roots):
            return True
        return "/" not in p and len(glob.glob(
            os.path.join(REPO, "raphtory_tpu", "**", p),
            recursive=True)) == 1

    with open(os.path.join(REPO, document)) as fh:
        text = re.sub(r"^```.*?^```", "", fh.read(), flags=re.M | re.S)
    dead = sorted({p for span in re.findall(r"`([^`]+)`", text)
                   for p in path.findall(span)
                   if not p.startswith("/") and not resolves(p)})
    assert dead == [], f"{document} names files that are not there"


# ---------------------------------------------------------------------------
# lock sanitizer


@pytest.fixture
def sanitizer():
    san = LockSanitizer().install(patch_jax=False)
    try:
        yield san
    finally:
        san.uninstall()


def test_sanitizer_detects_ab_ba_cycle(sanitizer):
    lock_a = threading.Lock()
    lock_b = threading.Lock()

    def nest(outer, inner):
        with outer:
            with inner:
                pass

    nest(lock_a, lock_b)
    t = threading.Thread(target=nest, args=(lock_b, lock_a))
    t.start()
    t.join()
    cycles = sanitizer.findings("lock-order-cycle")
    assert len(cycles) == 1
    sites = cycles[0]["sites"]
    assert len(sites) == 2 and len(set(sites)) == 2


def test_sanitizer_consistent_order_is_clean(sanitizer):
    lock_a = threading.Lock()
    lock_b = threading.Lock()

    def nest():
        with lock_a:
            with lock_b:
                pass

    threads = [threading.Thread(target=nest) for _ in range(4)]
    for t in threads:
        t.start()
    nest()
    for t in threads:
        t.join()
    assert sanitizer.findings() == []


def test_sanitizer_rlock_reentry_no_self_cycle(sanitizer):
    r = threading.RLock()
    with r:
        with r:
            pass
    assert sanitizer.findings() == []


def test_sanitizer_reports_lock_held_across_boundary(sanitizer):
    lock_a = threading.Lock()
    with lock_a:
        sanitizer.check_boundary("device_put")
    found = sanitizer.findings("lock-across-device-boundary")
    assert len(found) == 1
    assert found[0]["boundary"] == "device_put"
    # unheld crossing is silent, and a repeat of the same held-set is
    # reported once, not per call
    sanitizer.check_boundary("device_put")
    with lock_a:
        sanitizer.check_boundary("device_put")
    assert len(sanitizer.findings("lock-across-device-boundary")) == 1


def test_sanitizer_patches_real_device_put():
    san = LockSanitizer().install(patch_jax=True)
    try:
        import jax
        import numpy as np

        guard = threading.Lock()
        with guard:
            jax.device_put(np.arange(4))
        found = san.findings("lock-across-device-boundary")
        assert len(found) == 1 and found[0]["boundary"] == "device_put"
    finally:
        san.uninstall()


def test_sanitizer_condition_interop(sanitizer):
    # watermark.py wraps its Lock in a Condition — wait/notify must work
    # through the tracked proxy and keep the held-stack balanced
    lock = threading.Lock()
    cv = threading.Condition(lock)
    hits = []

    def waker():
        time.sleep(0.02)
        with cv:
            hits.append("woke")
            cv.notify_all()

    t = threading.Thread(target=waker)
    t.start()
    with cv:
        cv.wait(timeout=2)
    t.join()
    assert hits == ["woke"]
    assert sanitizer.findings() == []


def test_sanitizer_findings_reach_flight_recorder():
    from raphtory_tpu.obs.trace import Tracer

    tracer = Tracer(enabled=True, annotate=False)
    san = LockSanitizer(tracer=tracer).install(patch_jax=False)
    try:
        lock_a = threading.Lock()
        with lock_a:
            san.check_boundary("compile")
        names = [e["name"] for e in tracer.recent()]
        assert "sanitizer.lock-across-device-boundary" in names
    finally:
        san.uninstall()


def test_sanitizer_zero_overhead_when_disabled():
    # RTPU_SANITIZE unset → install() never ran → the factories are the
    # pristine implementations captured at import, not wrappers (the
    # zero-overhead claim: nothing to pay per acquire)
    if os.environ.get("RTPU_SANITIZE", "0") not in ("", "0", "false"):
        pytest.skip("sanitizer enabled for this whole run")
    assert threading.Lock is san_mod._RAW_LOCK
    assert threading.RLock is san_mod._RAW_RLOCK
    assert not hasattr(threading.Lock(), "_san")


def test_sanitizer_uninstall_restores_factories():
    # restores the PREVIOUS factories — under a process-wide
    # RTPU_SANITIZE install that is the outer sanitizer's wrapper, not
    # the raw C factory (restoring raw mid-suite left later locks
    # untracked and produced false race findings)
    prev_lock, prev_rlock = threading.Lock, threading.RLock
    san = LockSanitizer().install(patch_jax=False)
    assert threading.Lock is not prev_lock
    san.uninstall()
    assert threading.Lock is prev_lock
    assert threading.RLock is prev_rlock


# ---------------------------------------------------------------------------
# lockset race detector (Eraser) + extended device boundaries


def test_lockset_race_reproduced(sanitizer):
    """Inconsistent locking on a registered structure: one thread writes
    under the lock, another without — the candidate lockset empties and
    the race reports ONCE, keyed by the registration site."""
    tracker = sanitizer.register_shared("racy_table")
    lock = threading.Lock()

    def locked_writer():
        for _ in range(20):
            with lock:
                tracker.write()

    def unlocked_writer():
        for _ in range(20):
            tracker.write()

    a = threading.Thread(target=locked_writer)
    a.start(); a.join()
    b = threading.Thread(target=unlocked_writer)
    b.start(); b.join()
    races = sanitizer.findings("shared-state-race")
    assert len(races) == 1
    assert races[0]["name"] == "racy_table"
    assert "test_lint.py" in races[0]["site"]
    # already-reported trackers stay quiet
    tracker.write()
    assert len(sanitizer.findings("shared-state-race")) == 1


def test_lockset_consistent_locking_clean(sanitizer):
    tracker = sanitizer.register_shared("clean_table")
    lock = threading.Lock()

    def worker():
        for _ in range(20):
            with lock:
                tracker.write()
            with lock:
                tracker.read()

    threads = [threading.Thread(target=worker) for _ in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert sanitizer.findings("shared-state-race") == []


def test_lockset_single_thread_init_stays_lock_free(sanitizer):
    # Eraser's exclusive state: a structure built single-threaded needs
    # no lock until a second thread shows up
    tracker = sanitizer.register_shared("init_only")
    for _ in range(50):
        tracker.write()
    assert sanitizer.findings("shared-state-race") == []


def test_lockset_second_thread_read_only_is_not_a_race(sanitizer):
    # writes stay on thread 1; thread 2 only reads and both hold no lock
    # — shared (read-shared) state, not shared_modified: no report until
    # a WRITE happens with ≥2 threads involved
    tracker = sanitizer.register_shared("published")
    tracker.write()            # main thread, exclusive
    t = threading.Thread(target=tracker.read)
    t.start(); t.join()
    assert sanitizer.findings("shared-state-race") == []
    tracker.write()            # main thread writes in shared state, no lock
    assert len(sanitizer.findings("shared-state-race")) == 1


def test_lockset_clear_rearms(sanitizer):
    tracker = sanitizer.register_shared("rearmed")
    t = threading.Thread(target=tracker.write)
    t.start(); t.join()
    tracker.write()
    assert len(sanitizer.findings("shared-state-race")) == 1
    sanitizer.clear()
    assert sanitizer.findings() == []
    # state machine restarted: single-threaded again = clean
    tracker.write()
    assert sanitizer.findings("shared-state-race") == []


def test_track_shared_none_when_unset():
    # the zero-overhead contract: without an installed sanitizer the
    # instrumented structures carry a None tracker and pay one falsy
    # check per access
    if os.environ.get("RTPU_SANITIZE", "0") not in ("", "0", "false"):
        pytest.skip("sanitizer enabled for this whole run")
    from raphtory_tpu.analysis.sanitizer import track_shared
    from raphtory_tpu.core.sweep import FoldCache

    assert track_shared("anything") is None
    assert FoldCache(1 << 20)._san_tracker is None


def test_instrumented_structures_register_when_installed():
    import raphtory_tpu.analysis.sanitizer as sm

    # under a full-suite RTPU_SANITIZE run the process-wide sanitizer is
    # already active: install() is then a no-op and must NOT be torn
    # down by this test (uninstalling the global sanitizer mid-suite
    # would strip coverage from everything that runs after)
    was_active = sm.active() is not None and sm.active()._installed
    san = sm.install(patch_jax=False)
    before = len(san.findings("shared-state-race"))
    try:
        from raphtory_tpu.core.sweep import FoldCache
        from raphtory_tpu.utils import transfer as tr

        cache = FoldCache(1 << 20)
        assert cache._san_tracker is not None
        # only the SHARED engine registers (throwaway engines must not
        # leak permanent tracker registrations) — force a fresh one
        assert tr.TransferEngine(depth=1).stats._san_tracker is None
        prev_shared = tr._SHARED
        tr._SHARED = None
        try:
            eng = tr.shared_engine()
            assert eng.stats._san_tracker is not None
            names = {t.name for t in san.shared_trackers()}
            assert {"fold_cache", "transfer_stats"} <= names
            # consistent use through the real structures adds no NEW
            # race findings (the process-wide list may carry history)
            cache.put(("k",), "v", 64)
            cache.get(("k",))
            eng.stats.bump(slices=1)
            assert len(san.findings("shared-state-race")) == before
        finally:
            tr._SHARED = prev_shared
    finally:
        if not was_active:
            sm.uninstall()


def test_sanitizer_patches_device_get_and_block_until_ready():
    """The PR 8 satellite: the locks-held-across-device_put check covers
    the OTHER blocking jax entry points too."""
    san = LockSanitizer().install(patch_jax=True)
    try:
        import jax
        import numpy as np

        x = jax.device_put(np.arange(4))
        guard = threading.Lock()
        with guard:
            jax.device_get(x)
        found = san.findings("lock-across-device-boundary")
        assert [f["boundary"] for f in found] == ["device_get"]
        with guard:
            jax.block_until_ready(x)
        kinds = sorted(f["boundary"] for f in
                       san.findings("lock-across-device-boundary"))
        assert kinds == ["block_until_ready", "device_get"]
    finally:
        san.uninstall()
    # unpatch restored the real entry points
    import jax

    assert getattr(jax.device_get, "__wrapped__", None) is None


# ---------------------------------------------------------------------------
# RT012 collective-under-divergent-control-flow


RT012_POSITIVE = """
    import jax

    def sweep(x):
        if jax.process_index() == 0:
            return jax.lax.psum(x, "v")
        return x
"""


def test_collective_under_process_index_flagged():
    fs = lint(RT012_POSITIVE)
    assert rules_of(fs) == ["collective-under-divergent-control-flow"]
    assert "psum" in fs[0].message
    assert "process_index" in fs[0].message


def test_collective_under_timing_branch_flagged():
    # the accidental variant: a branch on a measured duration — every
    # process measures a different wall clock, so the arms diverge
    fs = lint("""
        import time
        import jax

        def sweep(x, budget):
            t0 = time.perf_counter()
            y = x + 1
            slow = time.perf_counter() - t0 > budget
            if slow:
                return jax.lax.pmean(y, "v")
            return y
    """)
    assert "collective-under-divergent-control-flow" in rules_of(fs)
    assert "slow" in fs[0].message


def test_transitive_dispatch_under_divergence_flagged():
    # the call does not NAME a collective — it resolves to a function
    # that dispatches one, and the fixpoint closure must see through it
    fs = lint("""
        import jax

        def exchange(x):
            return jax.lax.psum(x, "v")

        def run(x):
            if jax.process_index() == 0:
                return exchange(x)
            return x
    """)
    assert "collective-under-divergent-control-flow" in rules_of(fs)


def test_collective_divergence_spmd_uniform_suppressed():
    # a justified spmd-uniform pragma on the branch line is a reviewed
    # uniformity assertion — honoured
    fs = lint(RT012_POSITIVE.replace(
        "if jax.process_index() == 0:",
        "if jax.process_index() == 0:  "
        "# rtpulint: spmd-uniform - single-host path, all procs agree"))
    assert fs == []


def test_collective_divergence_empty_pragma_still_flags():
    # the pragma is an assertion, not a mute: with no justification the
    # finding stays, and the message says what is missing
    fs = lint(RT012_POSITIVE.replace(
        "if jax.process_index() == 0:",
        "if jax.process_index() == 0:  # rtpulint: spmd-uniform"))
    assert rules_of(fs) == ["collective-under-divergent-control-flow"]
    assert "EMPTY" in fs[0].message


def test_collective_under_uniform_branch_clean():
    # a branch on SPMD-uniform data (same value on every process) is the
    # idiomatic guard and must not fire
    fs = lint("""
        import jax

        def sweep(x, n_devices):
            if n_devices > 1:
                return jax.lax.psum(x, "v")
            return x
    """)
    assert fs == []


# ---------------------------------------------------------------------------
# RT013 unstable-compile-key


def test_traced_read_of_unkeyed_mutable_flagged():
    # (a) wrong-program-reuse: the traced body bakes in a module-level
    # mutable the lru_cache key does not carry
    fs = lint("""
        import functools
        import jax

        _SCALE = {"v": 2}

        @functools.lru_cache(maxsize=4)
        def compiled():
            def run(x):
                return x * _SCALE["v"]
            return jax.jit(run)
    """)
    assert "unstable-compile-key" in rules_of(fs)
    assert "_SCALE" in [f for f in fs
                        if f.name == "unstable-compile-key"][0].message


RT013_STORM = """
    import functools
    import time
    import jax

    @functools.lru_cache(maxsize=8)
    def compiled(tol):
        def run(x):
            return x * tol
        return jax.jit(run)

    def dispatch(x):
        dt = time.perf_counter()
        fn = compiled(dt)
        return fn(x)
"""


def test_timing_key_component_flagged():
    # (b) compile storm: a measured timing is a fresh float every call,
    # so the factory cache never hits and every dispatch recompiles
    fs = lint(RT013_STORM)
    assert "unstable-compile-key" in rules_of(fs)
    assert "compile storm" in [f for f in fs
                               if f.name == "unstable-compile-key"][0].message


def test_lambda_key_component_flagged():
    fs = lint("""
        import functools
        import jax

        @functools.lru_cache(maxsize=8)
        def compiled(fold):
            return jax.jit(lambda x: fold(x))

        def dispatch(x):
            fn = compiled(lambda v: v + 1)
            return fn(x)
    """)
    assert "unstable-compile-key" in rules_of(fs)
    assert "identity-keyed" in [
        f for f in fs if f.name == "unstable-compile-key"][0].message


def test_unstable_compile_key_suppressed():
    fs = lint(RT013_STORM.replace(
        "fn = compiled(dt)",
        "fn = compiled(dt)  # rtpulint: disable=unstable-compile-key"))
    assert "unstable-compile-key" not in rules_of(fs)


def test_stable_compile_key_clean():
    # the repo idiom: keys are quantised host ints (n_pad, k_pad) — no
    # finding on a stable hashable key
    fs = lint("""
        import functools
        import jax

        @functools.lru_cache(maxsize=8)
        def compiled(n_pad):
            def run(x):
                return x * n_pad
            return jax.jit(run)

        def dispatch(x, n_pad):
            fn = compiled(n_pad)
            return fn(x)
    """)
    assert "unstable-compile-key" not in rules_of(fs)


# ---------------------------------------------------------------------------
# RT014 resident-buffer-escape


RT014_CLOSURE = """
    import jax

    def step(state, delta):
        apply = jax.jit(lambda a, b: a + b, donate_argnums=(0,))

        def flush():
            return state.sum()

        out = apply(state, delta)
        return out, flush
"""


def test_donated_closure_capture_flagged():
    # the closure outlives the dispatch and late-binds to the donated
    # buffer — RT004's read-after dataflow cannot see this half
    fs = lint(RT014_CLOSURE)
    assert "resident-buffer-escape" in rules_of(fs)
    f = [f for f in fs if f.name == "resident-buffer-escape"][0]
    assert "flush" in f.message and "state" in f.message


def test_donated_container_store_flagged():
    # the stored reference (a registry/cache slot) dangles once XLA
    # reuses the donated pages
    fs = lint("""
        import jax

        def step(cache, state, delta):
            apply = jax.jit(lambda a, b: a + b, donate_argnums=(0,))
            cache["last"] = state
            out = apply(state, delta)
            return out
    """)
    assert "resident-buffer-escape" in rules_of(fs)
    assert "cache" in [f for f in fs
                       if f.name == "resident-buffer-escape"][0].message


def test_resident_escape_suppressed():
    fs = lint(RT014_CLOSURE.replace(
        "out = apply(state, delta)",
        "out = apply(state, delta)  "
        "# rtpulint: disable=resident-buffer-escape"))
    assert "resident-buffer-escape" not in rules_of(fs)


def test_rebound_after_dispatch_closure_clean():
    # rebinding the name after the donate means the late-bound closure
    # read sees the FRESH value — the documented fix
    fs = lint("""
        import jax

        def step(state, delta):
            apply = jax.jit(lambda a, b: a + b, donate_argnums=(0,))

            def flush():
                return state.sum()

            out = apply(state, delta)
            state = out
            return state, flush
    """)
    assert "resident-buffer-escape" not in rules_of(fs)


def test_overwritten_slot_clean():
    # the slot is overwritten with the dispatch result after the donate
    # — the stale reference is cleared, nothing dangles
    fs = lint("""
        import jax

        def step(cache, state, delta):
            apply = jax.jit(lambda a, b: a + b, donate_argnums=(0,))
            cache["last"] = state
            out = apply(state, delta)
            cache["last"] = out
            return out
    """)
    assert "resident-buffer-escape" not in rules_of(fs)


# ---------------------------------------------------------------------------
# RT015 device-op-on-ingest-path


RT015_POSITIVE = """
    import jax.numpy as jnp

    def push_batch(batch):
        return jnp.asarray(batch).sum()
"""


def test_device_op_in_ingest_module_flagged():
    fs = lint(RT015_POSITIVE, name="ingestion/pipeline.py")
    assert "device-op-on-ingest-path" in rules_of(fs)
    assert "jnp.asarray" in [f for f in fs
                             if f.name == "device-op-on-ingest-path"][0].message


def test_device_op_reachable_from_ingest_root_flagged():
    # the device op hides one call down — walk_from must surface it
    fs = lint("""
        import jax.numpy as jnp

        def _to_device(batch):
            return jnp.asarray(batch)

        def push_batch(batch):
            return _to_device(batch)
    """, name="obs/freshness.py")
    assert "device-op-on-ingest-path" in rules_of(fs)


def test_device_op_on_ingest_path_suppressed():
    fs = lint(RT015_POSITIVE.replace(
        "return jnp.asarray(batch).sum()",
        "return jnp.asarray(batch).sum()  "
        "# rtpulint: disable=device-op-on-ingest-path"),
        name="ingestion/pipeline.py")
    assert "device-op-on-ingest-path" not in rules_of(fs)


def test_host_side_jax_bookkeeping_on_ingest_clean():
    # process_index/device_count are pure host bookkeeping — safe
    fs = lint("""
        import jax

        def push_batch(batch):
            shard = len(batch) % max(1, jax.process_count())
            return shard
    """, name="ingestion/watermark.py")
    assert "device-op-on-ingest-path" not in rules_of(fs)


def test_device_op_outside_ingest_modules_clean():
    # the same source outside the ingest chain is the engine's job —
    # not this rule's business
    fs = lint(RT015_POSITIVE, name="core/sweep.py")
    assert "device-op-on-ingest-path" not in rules_of(fs)


# ---------------------------------------------------------------------------
# mesh-divergence sanitizer (the runtime half of RT012)


class _FakeTimer:
    """Injected in place of threading.Timer: captures the callback so
    tests drive the watchdog by hand instead of sleeping."""

    def __init__(self, interval, fn):
        self.interval, self.fn = interval, fn
        self.started = self.cancelled = False
        self.daemon = False

    def start(self):
        self.started = True

    def cancel(self):
        self.cancelled = True


def test_mesh_ring_bounded_and_seq_monotonic():
    san = san_mod.MeshSanitizer(capacity=4)
    seqs = [san.note_dispatch("site", "halo", f"S{i}", "i64")
            for i in range(6)]
    assert seqs == [0, 1, 2, 3, 4, 5]
    ring = san.ring()
    assert len(ring) == 4                      # old supersteps fell off
    assert [r["seq"] for r in ring] == [2, 3, 4, 5]
    block = san.status_block()
    assert block["dispatches"] == 6            # counter keeps the truth
    assert block["ring_capacity"] == 4
    assert block["findings"] == 0


def test_mesh_prefix_divergence_detects_first_mismatch():
    def rec(seq, shape):
        return {"seq": seq, "site": "a", "route": "halo",
                "shape": shape, "dtype": "i64"}

    agree = {0: [rec(0, "x"), rec(1, "y")],
             1: [rec(0, "x"), rec(1, "y")]}
    assert san_mod.mesh_prefix_divergence(agree) is None

    diverged = {0: [rec(0, "x"), rec(1, "y"), rec(2, "z")],
                1: [rec(0, "x"), rec(1, "Y"), rec(2, "Z")]}
    div = san_mod.mesh_prefix_divergence(diverged)
    assert div["seq"] == 1                     # FIRST divergent step
    assert div["process_a"] == 0 and div["process_b"] == 1
    assert div["fingerprint_a"] != div["fingerprint_b"]
    assert "y" in div["fingerprint_a"] and "Y" in div["fingerprint_b"]


def test_mesh_behind_peer_is_not_divergence():
    # a straggler (fewer dispatches, all common ones agreeing) is skew,
    # not divergence — that signal rides the per-process counters
    def rec(seq):
        return {"seq": seq, "site": "a", "route": "halo",
                "shape": "x", "dtype": "i64"}

    rings = {0: [rec(0), rec(1), rec(2)], 1: [rec(0)]}
    assert san_mod.mesh_prefix_divergence(rings) is None
    assert san_mod.mesh_prefix_divergence({0: [rec(0)]}) is None


def test_mesh_prefix_compares_only_common_window():
    # rings are bounded: only the overlapping seq window is comparable,
    # and a mismatch outside it must not (and cannot) be reported
    def rec(seq, shape):
        return {"seq": seq, "site": "a", "route": "halo",
                "shape": shape, "dtype": "i64"}

    rings = {0: [rec(s, "x") for s in range(0, 6)],
             1: [rec(s, "x" if s != 4 else "DIVERGED")
                 for s in range(3, 9)]}
    div = san_mod.mesh_prefix_divergence(rings)
    assert div is not None and div["seq"] == 4


def test_mesh_barrier_watchdog_fires_and_cancels():
    san = san_mod.MeshSanitizer(barrier_s=2.5, tracer=False,
                                timer_factory=_FakeTimer)
    t = san.barrier_watch("parallel.sharded.run/PageRank", "halo")
    assert t.started and t.daemon              # armed, never blocks exit
    t.fn()                                     # the barrier never returned
    found = san.findings("mesh-barrier-stall")
    assert len(found) == 1
    assert found[0]["site"] == "parallel.sharded.run/PageRank"
    assert found[0]["route"] == "halo"
    assert found[0]["seconds"] == 2.5
    assert san.status_block()["findings"] == 1
    # the happy path: the wait returns and the caller cancels
    t2 = san.barrier_watch("s", "replicate")
    t2.cancel()
    assert t2.cancelled
    assert len(san.findings("mesh-barrier-stall")) == 1


def test_mesh_barrier_watchdog_disarmed_by_default(monkeypatch):
    monkeypatch.delenv("RTPU_SANITIZE_BARRIER_S", raising=False)
    san = san_mod.MeshSanitizer(timer_factory=_FakeTimer)
    assert san.barrier_s == 0.0
    assert san.barrier_watch("s", "halo") is None   # nothing armed
    monkeypatch.setenv("RTPU_SANITIZE_BARRIER_S", "1.5")
    assert san_mod.MeshSanitizer().barrier_s == 1.5
    monkeypatch.setenv("RTPU_SANITIZE_BARRIER_S", "nonsense")
    assert san_mod.MeshSanitizer().barrier_s == 0.0


def test_mesh_dispatch_and_stall_journaled():
    class _FakeJournal:
        def __init__(self):
            self.records = []

        def emit(self, kind, data, **kw):
            self.records.append((kind, dict(data)))

    j = _FakeJournal()
    san = san_mod.MeshSanitizer(barrier_s=1.0, tracer=False,
                                timer_factory=_FakeTimer)
    san._journal = j
    san.note_dispatch("site", "halo", "S4W2", "i64")
    t = san.barrier_watch("site", "halo")
    t.fn()
    kinds = [(k, d["event"]) for k, d in j.records]
    assert kinds == [("mesh", "dispatch"), ("mesh", "mesh-barrier-stall")]
    disp = j.records[0][1]
    assert disp["seq"] == 0 and disp["shape"] == "S4W2"


def test_mesh_disarmed_is_free():
    # RTPU_SANITIZE unset → mesh_active() is None and every hook is one
    # module-global falsy check; /statusz reports the stub block
    prev = san_mod._MESH
    san_mod.mesh_uninstall()
    try:
        assert san_mod.mesh_active() is None
        san_mod.note_mesh_dispatch("s", "halo", "x", "i64")   # no-op
        assert san_mod.mesh_barrier_watch("s", "halo") is None
        from raphtory_tpu.jobs.rest import _mesh_sanitizer_block
        assert _mesh_sanitizer_block() == {"enabled": False}
    finally:
        san_mod._MESH = prev


def test_mesh_install_lifecycle_and_statusz():
    prev = san_mod._MESH
    san_mod.mesh_uninstall()
    try:
        san = san_mod.mesh_install(capacity=8)
        assert san_mod.mesh_install() is san   # idempotent
        assert san_mod.mesh_active() is san
        san_mod.note_mesh_dispatch("s", "halo", "x", "i64")
        assert len(san.ring()) == 1
        from raphtory_tpu.jobs.rest import _mesh_sanitizer_block
        block = _mesh_sanitizer_block()
        assert block["enabled"] is True and block["dispatches"] == 1
        san.clear()
        assert san.ring() == [] and san.status_block()["dispatches"] == 0
    finally:
        san_mod._MESH = prev


def test_postmortem_mesh_divergence_from_journal_records():
    from raphtory_tpu.analysis import postmortem

    def mesh_rec(p, seq, shape):
        return {"k": "mesh", "p": p,
                "d": {"event": "dispatch", "seq": seq, "site": "a",
                      "route": "halo", "shape": shape, "dtype": "i64"}}

    records = [
        mesh_rec(0, 0, "x"), mesh_rec(1, 0, "x"),
        mesh_rec(0, 1, "x"), mesh_rec(1, 1, "DIVERGED"),
        # non-dispatch mesh events and other kinds must be ignored
        {"k": "mesh", "p": 0, "d": {"event": "mesh-barrier-stall"}},
        {"k": "fault", "p": 0, "d": {"seq": 1}},
    ]
    div = postmortem.mesh_divergence(records)
    assert div is not None and div["seq"] == 1
    assert {div["process_a"], div["process_b"]} == {0, 1}
    # a single process's records cannot diverge
    assert postmortem.mesh_divergence(records[:1]) is None
    assert postmortem.mesh_divergence([]) is None

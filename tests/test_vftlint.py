"""vftlint: the repo is clean, and every rule both fires and suppresses.

Two layers:

- **tier-1 guard**: the full rule suite over this checkout returns zero
  findings (any unannotated regression in jit-purity / host-sync /
  thread-shared-state / explicit-dtype / fault-barrier / fast-registry /
  lock-order / guarded-by / blocking-under-lock / use-after-donate /
  recompile-hygiene / wire-dtype / telemetry-schema fails this module);
- **fixture tests**: per rule, a seeded violation in a tmp tree fires and
  the annotated/clean form stays quiet — the acceptance contract that no
  rule is satisfied by blanket allowlisting.

Also pinned here: the parse-once budget (every source parsed exactly once
per run regardless of rule count, plus a generous wall-clock ceiling) and
the :class:`LockOrderWatch` runtime shim the daemon tests wrap their named
locks with.

Pure AST work, no jax import, no compiles — registered in _FAST_MODULES.
"""

import os
import sys
import textwrap

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from tools.vftlint import all_rules, run_lint  # noqa: E402
from tools.vftlint.__main__ import main as vftlint_main  # noqa: E402
from tools.vftlint.locks import LockOrderWatch  # noqa: E402
from tools.vftlint.rules import fast_registry, lock_order  # noqa: E402

ALL_RULE_IDS = {
    "blocking-under-lock", "explicit-dtype", "fast-registry",
    "fault-barrier", "guarded-by", "host-sync", "jit-purity",
    "lock-order", "recompile-hygiene", "telemetry-schema",
    "thread-shared-state", "use-after-donate", "wire-dtype",
}


def write(root, rel, text):
    path = root / rel
    path.parent.mkdir(parents=True, exist_ok=True)
    path.write_text(textwrap.dedent(text))
    return path


def lint(root, rule):
    return [str(f) for f in run_lint(str(root), [rule])]


# ---- tier-1 guard ---------------------------------------------------------


def test_registry_ships_all_rules():
    assert set(all_rules()) == ALL_RULE_IDS


def test_repo_is_clean():
    findings = run_lint(REPO)
    assert findings == [], "\n".join(str(f) for f in findings)


def test_cli_clean_exit(capsys):
    assert vftlint_main([REPO]) == 0
    assert "clean" in capsys.readouterr().out


def test_cli_unknown_rule_is_usage_error(capsys):
    assert vftlint_main(["--rule", "no-such-rule", REPO]) == 2
    assert "unknown rule" in capsys.readouterr().err


def test_cli_findings_exit(tmp_path, capsys):
    write(tmp_path, "video_features_tpu/models/m.py",
          "import jax.numpy as jnp\nx = jnp.asarray([1.0])\n")
    assert vftlint_main([str(tmp_path)]) == 1
    out = capsys.readouterr().out
    assert "explicit-dtype" in out and "models/m.py:2" in out


def test_cli_list_rules(capsys):
    assert vftlint_main(["--list-rules"]) == 0
    out = capsys.readouterr().out
    for rule_id in ALL_RULE_IDS:
        assert rule_id in out


# ---- jit-purity -----------------------------------------------------------

JIT_IMPURE = """
    import time
    import jax

    @jax.jit
    def step(x):
        print("tracing", x.shape)
        t = time.time()
        return x * t
"""

JIT_WRAPPED = """
    class E:
        def make(self):
            def step(params, x):
                import random
                return x * random.random()
            return self.runner.jit(step)
"""


def test_jit_purity_fires_on_decorated(tmp_path):
    write(tmp_path, "video_features_tpu/bad.py", JIT_IMPURE)
    found = lint(tmp_path, "jit-purity")
    assert any("'print()'" in f and "bad.py:7" in f for f in found)
    assert any("time.time" in f for f in found)


def test_jit_purity_fires_through_runner_jit(tmp_path):
    write(tmp_path, "video_features_tpu/bad.py", JIT_WRAPPED)
    found = lint(tmp_path, "jit-purity")
    assert any("stdlib 'random.random()'" in f for f in found)


def test_jit_purity_fires_through_shard_map(tmp_path):
    write(tmp_path, "video_features_tpu/bad.py", """
        def fwd(params, frames, mesh):
            def local(p, fr):
                print(fr.shape)
                return fr
            return shard_map(local, mesh=mesh)(params, frames)
    """)
    assert any("'print()'" in f for f in lint(tmp_path, "jit-purity"))


def test_jit_purity_quiet_on_clean_and_untraced(tmp_path):
    write(tmp_path, "video_features_tpu/ok.py", """
        import jax

        @jax.jit
        def step(x):
            return x * 2

        def host_loop(xs):  # not traced: host effects are fine here
            for x in xs:
                print(x)
    """)
    assert lint(tmp_path, "jit-purity") == []


def test_jit_purity_annotation_suppresses_with_reason(tmp_path):
    write(tmp_path, "video_features_tpu/ok.py", """
        import jax

        @jax.jit
        def step(x):
            # jit-purity: trace-time banner, deliberately prints once per compile
            print("compiling")
            return x
    """)
    assert lint(tmp_path, "jit-purity") == []


def test_empty_annotation_reason_is_a_finding(tmp_path):
    write(tmp_path, "video_features_tpu/bad.py", """
        import jax

        @jax.jit
        def step(x):
            print("hi")  # jit-purity:
            return x
    """)
    found = lint(tmp_path, "jit-purity")
    assert any("no reason" in f for f in found)
    assert any("'print()'" in f for f in found)  # not suppressed either


# ---- host-sync ------------------------------------------------------------

HOST_SYNC_BAD = """
    import numpy as np

    class E:
        def extract(self, path):
            feats = self._step(self.params, path)
            a = np.asarray(feats)
            b = float(feats)
            c = feats.item()
            return a, b, c
"""

HOST_SYNC_OK = """
    import numpy as np

    class E:
        def extract(self, path):
            feats = self._step(self.params, path)
            host = self._wait(feats)          # the accounted site
            meta_fps = np.asarray([25.0])     # host data: not flagged
            return host, meta_fps
"""


def test_host_sync_fires_on_unaccounted_sinks(tmp_path):
    write(tmp_path, "video_features_tpu/extractors/bad.py", HOST_SYNC_BAD)
    found = lint(tmp_path, "host-sync")
    assert any("np.asarray()" in f for f in found)
    assert any("float()" in f for f in found)
    assert any(".item()" in f for f in found)


def test_host_sync_quiet_when_routed_through_wait(tmp_path):
    write(tmp_path, "video_features_tpu/extractors/ok.py", HOST_SYNC_OK)
    assert lint(tmp_path, "host-sync") == []


def test_host_sync_tracks_params_and_unpacking(tmp_path):
    write(tmp_path, "video_features_tpu/extractors/bad.py", """
        import numpy as np

        class E:
            def extract(self, x):
                feats, logits = self._flow_step(self.params, x)
                fc = self.params["fc"]
                a = np.asarray(logits)   # tainted via tuple unpack
                b = np.asarray(fc["kernel"])  # tainted via *params attr
                return a @ b
    """)
    found = lint(tmp_path, "host-sync")
    assert len([f for f in found if "np.asarray()" in f]) == 2


def test_host_sync_fires_inside_traced_body(tmp_path):
    write(tmp_path, "video_features_tpu/models/bad.py", """
        import numpy as np
        import jax

        @jax.jit
        def step(x):
            return np.asarray(x) * 2
    """)
    assert any("mid-trace" in f for f in lint(tmp_path, "host-sync"))


def test_host_sync_branch_rewait_is_not_flagged(tmp_path):
    """A value re-assigned from _wait INSIDE a branch is host there — the
    sink check must see the in-branch state, not the pre-block taint."""
    write(tmp_path, "video_features_tpu/extractors/ok.py", """
        import numpy as np

        class E:
            def extract(self, x, debug):
                feats = self._step(self.params, x)
                if debug:
                    feats = self._wait(feats)
                    logits = np.asarray(feats) * 2.0
                return feats
    """)
    assert lint(tmp_path, "host-sync") == []


def test_host_sync_else_branch_keeps_pre_branch_taint(tmp_path):
    """The if-arm's _wait kill must not leak into the else arm."""
    write(tmp_path, "video_features_tpu/extractors/bad.py", """
        import numpy as np

        class E:
            def extract(self, x, debug):
                feats = self._step(self.params, x)
                if debug:
                    feats = self._wait(feats)
                else:
                    feats = np.asarray(feats)
                return feats
    """)
    assert any("np.asarray()" in f for f in lint(tmp_path, "host-sync"))


def test_host_sync_annotation_suppresses(tmp_path):
    write(tmp_path, "video_features_tpu/extractors/ok.py", """
        import numpy as np

        class E:
            def warm(self, x):
                # host-sync: warmup thread, off the critical path
                np.asarray(self._step(self.params, x))
    """)
    assert lint(tmp_path, "host-sync") == []


# ---- thread-shared-state --------------------------------------------------


def test_thread_rule_fires_on_undeclared_module(tmp_path):
    write(tmp_path, "video_features_tpu/sneaky.py", """
        import threading

        def go(fn):
            threading.Thread(target=fn, daemon=True).start()
    """)
    found = lint(tmp_path, "thread-shared-state")
    assert any("no declared threading seam" in f for f in found)


def test_thread_rule_fires_on_unannotated_shared_store(tmp_path):
    # declared module path, declared site — but the annotation is missing
    write(tmp_path, "video_features_tpu/io/output.py", """
        import threading

        class W:
            def start(self):
                threading.Thread(target=self._drain).start()

            def _drain(self):
                handle = self._q.get()
                handle._error = ValueError("x")
    """)
    found = lint(tmp_path, "thread-shared-state")
    assert any("without a '# thread-shared-state:" in f for f in found)
    # declared in SHARED_WRITES, so no 'not declared' finding for this site
    assert not any("not declared" in f for f in found)


def test_thread_rule_fires_on_undeclared_shared_store(tmp_path):
    write(tmp_path, "video_features_tpu/io/output.py", """
        import threading

        class W:
            def start(self):
                threading.Thread(target=self._drain).start()

            def _drain(self):
                handle = self._q.get()
                handle._error = 1  # thread-shared-state: before the Event
                handle._extra = 2  # thread-shared-state: sounds legit
    """)
    found = lint(tmp_path, "thread-shared-state")
    undeclared = [f for f in found if "not declared in SHARED_WRITES" in f]
    assert len(undeclared) == 1 and "handle._extra" in undeclared[0]


def test_thread_rule_exempts_thread_private_objects(tmp_path):
    """Stores to an object constructed inside the thread entry are
    thread-private until published — not shared state."""
    write(tmp_path, "video_features_tpu/io/output.py", """
        import threading

        class W:
            def start(self):
                threading.Thread(target=self._drain).start()

            def _drain(self):
                handle = self._q.get()
                handle._error = 1  # thread-shared-state: before the Event
                meta = Thing()
                meta.count = 0
                self._q2.put(meta)
    """)
    found = lint(tmp_path, "thread-shared-state")
    assert not any("meta.count" in f for f in found)
    assert found == []  # handle._error annotated + declared; nothing else


def test_thread_rule_empty_annotation_reason_message(tmp_path):
    """A reasonless annotation reports 'no reason', not 'without a ...
    annotation' — the developer already wrote the comment."""
    write(tmp_path, "video_features_tpu/io/output.py", """
        import threading

        class W:
            def start(self):
                threading.Thread(target=self._drain).start()

            def _drain(self):
                handle = self._q.get()
                handle._error = 1  # thread-shared-state:
    """)
    found = lint(tmp_path, "thread-shared-state")
    assert any("no reason" in f for f in found)
    assert not any("without a" in f for f in found)


def test_thread_rule_reports_stale_declarations(tmp_path):
    # the declared module spawns a thread whose target stores nothing:
    # every declared site for it is stale
    write(tmp_path, "video_features_tpu/io/output.py", """
        import threading

        def start(fn):
            threading.Thread(target=fn).start()
    """)
    found = lint(tmp_path, "thread-shared-state")
    assert any("stale declaration" in f and "handle._error" in f
               for f in found)


def test_thread_rule_quiet_on_threadless_module(tmp_path):
    write(tmp_path, "video_features_tpu/plain.py",
          "def f(x):\n    return x + 1\n")
    assert lint(tmp_path, "thread-shared-state") == []


# ---- explicit-dtype -------------------------------------------------------


def test_explicit_dtype_fires_in_models_and_ops(tmp_path):
    write(tmp_path, "video_features_tpu/models/m.py", """
        import jax.numpy as jnp
        MEAN = jnp.asarray([0.43, 0.39, 0.37])
        Z = jnp.zeros((3, 3))
        R = jnp.arange(10)
    """)
    found = lint(tmp_path, "explicit-dtype")
    assert len(found) == 3
    assert all("explicit-dtype" in f for f in found)


def test_explicit_dtype_quiet_on_dtyped_and_like(tmp_path):
    write(tmp_path, "video_features_tpu/ops/o.py", """
        import jax.numpy as jnp

        def f(x):
            a = jnp.asarray([1.0], jnp.float32)       # positional dtype
            b = jnp.zeros((2, 2), dtype=jnp.int32)    # keyword dtype
            c = jnp.arange(4, dtype=jnp.int32)
            d = jnp.zeros_like(x)                     # inherits dtype
            return a, b, c, d
    """)
    assert lint(tmp_path, "explicit-dtype") == []


def test_explicit_dtype_out_of_scope_dirs_are_ignored(tmp_path):
    # host-side code (io/, utils/) may promote freely
    write(tmp_path, "video_features_tpu/io/h.py",
          "import jax.numpy as jnp\nx = jnp.asarray([1.0])\n")
    assert lint(tmp_path, "explicit-dtype") == []


def test_explicit_dtype_annotation_suppresses(tmp_path):
    write(tmp_path, "video_features_tpu/models/m.py", """
        import jax.numpy as jnp
        # explicit-dtype: promotion wanted — follows the input's dtype knob
        MEAN = jnp.asarray([0.43])
    """)
    assert lint(tmp_path, "explicit-dtype") == []


# ---- fault-barrier (migrated rule) ----------------------------------------


def test_fault_barrier_rule_fires_via_framework(tmp_path):
    write(tmp_path, "video_features_tpu/sneaky.py",
          "try:\n    pass\nexcept Exception:\n    pass\n")
    found = lint(tmp_path, "fault-barrier")
    assert any("fault-barrier" in f and "sneaky.py:3" in f for f in found)
    assert any("no declared barriers" in f for f in found)


def test_fault_barrier_rule_quiet_on_clean_tree(tmp_path):
    write(tmp_path, "video_features_tpu/fine.py",
          "try:\n    pass\nexcept ValueError:\n    pass\n")
    assert lint(tmp_path, "fault-barrier") == []


def test_shim_still_works():
    """python tools/lint_fault_barrier.py keeps its PR-1 contract."""
    import subprocess

    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "lint_fault_barrier.py")],
        capture_output=True, text=True)
    assert proc.returncode == 0, proc.stderr
    assert "no strays" in proc.stdout


# ---- fast-registry --------------------------------------------------------


def _tiered_tree(tmp_path):
    write(tmp_path, "tests/conftest.py",
          '_FAST_MODULES = {\n    "test_a",\n}\n')
    write(tmp_path, "tests/test_a.py", "def test_x():\n    pass\n")
    write(tmp_path, "tests/test_b.py",
          "import pytest\npytestmark = pytest.mark.slow\n")


def test_fast_registry_quiet_on_tiered_modules(tmp_path, monkeypatch):
    monkeypatch.setattr(fast_registry, "DEFAULT_TIER", {})
    _tiered_tree(tmp_path)
    assert lint(tmp_path, "fast-registry") == []


def test_fast_registry_fires_on_untiered_module(tmp_path, monkeypatch):
    monkeypatch.setattr(fast_registry, "DEFAULT_TIER", {})
    _tiered_tree(tmp_path)
    write(tmp_path, "tests/test_c.py", "def test_y():\n    pass\n")
    found = lint(tmp_path, "fast-registry")
    assert len(found) == 1 and "'test_c' is in no tier" in found[0]


def test_fast_registry_default_tier_needs_annotation(tmp_path, monkeypatch):
    monkeypatch.setattr(fast_registry, "DEFAULT_TIER",
                        {"test_c": "mid-weight"})
    _tiered_tree(tmp_path)
    write(tmp_path, "tests/test_c.py", "def test_y():\n    pass\n")
    found = lint(tmp_path, "fast-registry")
    assert len(found) == 1 and "carries no" in found[0]
    # the annotated form is quiet
    write(tmp_path, "tests/test_c.py",
          "# fast-registry: mid-weight, compiles too heavy for fast\n"
          "def test_y():\n    pass\n")
    assert lint(tmp_path, "fast-registry") == []


def test_fast_registry_rejects_reasonless_annotation(tmp_path, monkeypatch):
    monkeypatch.setattr(fast_registry, "DEFAULT_TIER",
                        {"test_c": "mid-weight"})
    _tiered_tree(tmp_path)
    write(tmp_path, "tests/test_c.py",
          "# fast-registry:\ndef test_y():\n    pass\n")
    found = lint(tmp_path, "fast-registry")
    assert len(found) == 1 and "has no reason" in found[0]


def test_fast_registry_reports_stale_default_tier_entry(tmp_path, monkeypatch):
    monkeypatch.setattr(fast_registry, "DEFAULT_TIER", {"test_gone": "x"})
    _tiered_tree(tmp_path)
    found = lint(tmp_path, "fast-registry")
    assert any("no such test module" in f for f in found)


def test_fast_registry_missing_conftest(tmp_path):
    write(tmp_path, "tests/test_a.py", "def test_x():\n    pass\n")
    found = lint(tmp_path, "fast-registry")
    assert any("registry is missing" in f for f in found)


# ---- lock-order -----------------------------------------------------------

TWO_LOCKS = """
    import threading

    class S:
        def __init__(self):
            self._a = threading.Lock()
            self._b = threading.Lock()
"""
A = "video_features_tpu/locky.py:S._a"
B = "video_features_tpu/locky.py:S._b"


def _locky(tmp_path, body):
    # body joins TWO_LOCKS *inside* class S (8 = the class-body indent in
    # the raw fixture string, which write() dedents by 4)
    write(tmp_path, "video_features_tpu/locky.py",
          TWO_LOCKS + textwrap.indent(textwrap.dedent(body), "        "))


def test_lock_order_fires_on_inversion(tmp_path, monkeypatch):
    monkeypatch.setattr(lock_order, "LOCK_ORDER", [B, A])
    _locky(tmp_path, """
        def fwd(self):
            with self._a:
                with self._b:
                    pass
    """)
    found = lint(tmp_path, "lock-order")
    assert len(found) == 1 and "inversion" in found[0]
    assert "S._a" in found[0] and "S._b" in found[0]


def test_lock_order_quiet_when_order_matches(tmp_path, monkeypatch):
    monkeypatch.setattr(lock_order, "LOCK_ORDER", [A, B])
    _locky(tmp_path, """
        def fwd(self):
            with self._a:
                with self._b:
                    pass
    """)
    assert lint(tmp_path, "lock-order") == []


def test_lock_order_fires_on_cycle(tmp_path, monkeypatch):
    monkeypatch.setattr(lock_order, "LOCK_ORDER", [A, B])
    _locky(tmp_path, """
        def fwd(self):
            with self._a:
                with self._b:
                    pass

        def rev(self):
            with self._b:
                with self._a:
                    pass
    """)
    found = lint(tmp_path, "lock-order")
    assert any("cycle" in f for f in found)
    assert any("inversion" in f for f in found)  # rev() also inverts


def test_lock_order_follows_helper_calls(tmp_path, monkeypatch):
    """Interprocedural: the nested acquisition lives two frames down."""
    monkeypatch.setattr(lock_order, "LOCK_ORDER", [B, A])
    _locky(tmp_path, """
        def outer(self):
            with self._a:
                self._inner()

        def _inner(self):
            self._innermost()

        def _innermost(self):
            with self._b:
                pass
    """)
    found = lint(tmp_path, "lock-order")
    assert len(found) == 1 and "inversion" in found[0] and "via" in found[0]
    # the declared direction is quiet
    monkeypatch.setattr(lock_order, "LOCK_ORDER", [A, B])
    assert lint(tmp_path, "lock-order") == []


def test_lock_order_unordered_nesting_is_a_finding(tmp_path):
    # no monkeypatch: the fixture locks have no LOCK_ORDER position, and
    # nesting is exactly the moment a lock must be named and ordered
    _locky(tmp_path, """
        def fwd(self):
            with self._a:
                with self._b:
                    pass
    """)
    found = lint(tmp_path, "lock-order")
    assert any("no LOCK_ORDER position" in f for f in found)


def test_lock_order_self_deadlock_on_plain_lock(tmp_path, monkeypatch):
    monkeypatch.setattr(lock_order, "LOCK_ORDER", [A, B])
    _locky(tmp_path, """
        def f(self):
            with self._a:
                with self._a:
                    pass
    """)
    found = lint(tmp_path, "lock-order")
    assert len(found) == 1 and "self-deadlock" in found[0]


def test_lock_order_rlock_reentry_is_fine(tmp_path):
    write(tmp_path, "video_features_tpu/locky.py", """
        import threading

        class S:
            def __init__(self):
                self._r = threading.RLock()

            def f(self):
                with self._r:
                    with self._r:
                        pass
    """)
    assert lint(tmp_path, "lock-order") == []


def test_lock_order_annotation_suppresses(tmp_path, monkeypatch):
    monkeypatch.setattr(lock_order, "LOCK_ORDER", [B, A])
    _locky(tmp_path, """
        def fwd(self):
            with self._a:
                # lock-order: teardown-only path; b's owner thread is joined
                with self._b:
                    pass
    """)
    assert lint(tmp_path, "lock-order") == []


# ---- guarded-by -----------------------------------------------------------

JOURNAL_OK = """
    import threading

    class SpanJournal:
        def __init__(self):
            self._lock = threading.Lock()
            self.emitted = 0
            self.dropped = 0

        def emit(self, rec):
            with self._lock:
                self.emitted += 1
                self.dropped += 0
"""


def test_guarded_by_quiet_on_locked_access(tmp_path):
    write(tmp_path, "video_features_tpu/obs/journal.py", JOURNAL_OK)
    assert lint(tmp_path, "guarded-by") == []


def test_guarded_by_fires_on_off_lock_read(tmp_path):
    write(tmp_path, "video_features_tpu/obs/journal.py", JOURNAL_OK + """
        def stats(self):
            return {"emitted": self.emitted}
""")
    found = lint(tmp_path, "guarded-by")
    assert len(found) == 1
    assert "self.emitted" in found[0] and "'journal'" in found[0]


def test_guarded_by_fires_on_off_lock_dict_iteration(tmp_path):
    write(tmp_path, "video_features_tpu/obs/metrics.py", """
        import threading

        class MetricsRegistry:
            def __init__(self):
                self._lock = threading.Lock()
                self._counters = {}
                self._gauges = {}
                self._hists = {}

            def inc(self, k):
                with self._lock:
                    self._counters[k] = self._gauges.get(k, 0)
                    self._hists[k] = 1

            def snapshot(self):
                return sorted(self._counters.items())
    """)
    found = lint(tmp_path, "guarded-by")
    assert len(found) == 1 and "self._counters" in found[0]
    assert "snapshot" in found[0]


def test_guarded_by_locked_suffix_is_exempt(tmp_path):
    write(tmp_path, "video_features_tpu/obs/journal.py", JOURNAL_OK + """
        def stats_locked(self):
            return self.emitted + self.dropped
""")
    assert lint(tmp_path, "guarded-by") == []


def test_guarded_by_annotation_suppresses(tmp_path):
    write(tmp_path, "video_features_tpu/obs/journal.py", JOURNAL_OK + """
        def stats(self):
            # guarded-by: GIL-atomic monotone int; off-by-one-moment is fine
            return self.emitted
""")
    assert lint(tmp_path, "guarded-by") == []


def test_guarded_by_reports_stale_declaration(tmp_path):
    write(tmp_path, "video_features_tpu/obs/journal.py", """
        import threading

        class SpanJournal:
            def __init__(self):
                self._lock = threading.Lock()
                self.emitted = 0

            def emit(self):
                with self._lock:
                    self.emitted += 1
    """)
    found = lint(tmp_path, "guarded-by")
    assert len(found) == 1
    assert "stale" in found[0] and "self.dropped" in found[0]


# ---- blocking-under-lock --------------------------------------------------

MU = """
    import threading
    import time

    class S:
        def __init__(self):
            self._mu = threading.Lock()
            self._q = None
"""


def _blocky(tmp_path, body):
    # body joins MU *inside* class S (see _locky)
    write(tmp_path, "video_features_tpu/blocky.py",
          MU + textwrap.indent(textwrap.dedent(body), "        "))


def test_blocking_fires_on_sleep_under_lock(tmp_path):
    _blocky(tmp_path, """
        def bad(self):
            with self._mu:
                time.sleep(0.1)
    """)
    found = lint(tmp_path, "blocking-under-lock")
    assert len(found) == 1 and "time.sleep()" in found[0]


def test_blocking_quiet_outside_lock(tmp_path):
    _blocky(tmp_path, """
        def ok(self):
            with self._mu:
                x = 1
            time.sleep(0.1)
            return x
    """)
    assert lint(tmp_path, "blocking-under-lock") == []


def test_blocking_follows_helper_calls(tmp_path):
    _blocky(tmp_path, """
        def bad(self):
            with self._mu:
                self._flush()

        def _flush(self):
            with open("/tmp/x") as f:
                return f.read()
    """)
    found = lint(tmp_path, "blocking-under-lock")
    assert len(found) == 1
    assert "via S._flush" in found[0] and "open()" in found[0]


def test_blocking_queue_put_vs_put_nowait(tmp_path):
    _blocky(tmp_path, """
        def bad(self, item):
            with self._mu:
                self._q.put(item)

        def ok(self, item):
            with self._mu:
                self._q.put_nowait(item)
    """)
    found = lint(tmp_path, "blocking-under-lock")
    assert len(found) == 1 and "queue .put()" in found[0]
    assert "bad" in found[0]


def test_blocking_device_sync_under_lock(tmp_path):
    _blocky(tmp_path, """
        def bad(self, feats):
            with self._mu:
                return self._wait(feats)
    """)
    found = lint(tmp_path, "blocking-under-lock")
    assert len(found) == 1 and "._wait()" in found[0]


def test_blocking_nested_def_is_not_under_the_lock(tmp_path):
    """A def/lambda created under a lock runs later, lock-free."""
    _blocky(tmp_path, """
        def ok(self):
            with self._mu:
                def worker():
                    time.sleep(1.0)
                self._worker = worker
    """)
    assert lint(tmp_path, "blocking-under-lock") == []


def test_blocking_annotation_suppresses(tmp_path):
    _blocky(tmp_path, """
        def shutdown(self):
            with self._mu:
                # blocking-under-lock: teardown path; no producer is live
                time.sleep(0.01)
    """)
    assert lint(tmp_path, "blocking-under-lock") == []


# ---- use-after-donate -----------------------------------------------------

# the PR-13 wiring shape: jit_paged forwards its fn into sharded_apply,
# which donates argnum 2 — discovered (not hardcoded) by prepare()
DONATE_MESH = """
    import jax

    def sharded_apply(mesh, fn, donate_argnums=()):
        return jax.jit(fn, donate_argnums=donate_argnums)

    class MeshRunner:
        def jit_paged(self, fn):
            return sharded_apply(self.mesh, fn, donate_argnums=(2,))
"""


def test_donate_fires_on_read_after_direct_donation(tmp_path):
    write(tmp_path, "video_features_tpu/parallel/bad.py", """
        import jax

        class R:
            def run(self, step, x):
                fn = jax.jit(step, donate_argnums=(0,))
                buf = self.runner.put(x)
                out = fn(buf)
                return out + buf
    """)
    found = lint(tmp_path, "use-after-donate")
    assert len(found) == 1
    assert "'buf' is read after its buffer was donated" in found[0]
    assert "jax.jit(donate_argnums=(0,))" in found[0]


def test_donate_fires_through_helper_frame_naming_the_chain(tmp_path):
    """Donation through the discovered wiring wrapper: the finding names
    the via-call chain jit_paged → sharded_apply."""
    write(tmp_path, "video_features_tpu/parallel/mesh.py", DONATE_MESH)
    write(tmp_path, "video_features_tpu/extractors/bad.py", """
        class E:
            def pack_spec(self, step, rows, page):
                fn = self.runner.jit_paged(step)
                table = self.runner.put(rows)
                out = fn(self.params, page, table)
                return self._wait(table)
    """)
    found = lint(tmp_path, "use-after-donate")
    assert len(found) == 1 and "bad.py:7" in found[0]
    assert "donated at line 6" in found[0]
    assert "jit_paged → sharded_apply(donate_argnums=(2,))" in found[0]
    assert "video_features_tpu/parallel/mesh.py" in found[0]


def test_donate_quiet_when_rebound_from_output(tmp_path):
    """The paged contract: the donated table comes back as an output —
    rebinding the name to the returned buffer is the sanctioned idiom."""
    write(tmp_path, "video_features_tpu/parallel/mesh.py", DONATE_MESH)
    write(tmp_path, "video_features_tpu/extractors/ok.py", """
        class E:
            def pack_spec(self, step, rows, page):
                fn = self.runner.jit_paged(step)
                table = self.runner.put(rows)
                out, table = fn(self.params, page, table)
                return self._wait(table)
    """)
    assert lint(tmp_path, "use-after-donate") == []


def test_donate_host_values_are_not_tracked(tmp_path):
    """Passing a host array donates the transient device copy; the host
    original stays valid (the packer's row-table path relies on this)."""
    write(tmp_path, "video_features_tpu/parallel/ok.py", """
        import jax
        import numpy as np

        class R:
            def run(self, step, rows):
                fn = jax.jit(step, donate_argnums=(0,))
                host = np.stack(rows)
                out = fn(host)
                return out, host.shape
    """)
    assert lint(tmp_path, "use-after-donate") == []


def test_donate_fires_on_loop_without_restage(tmp_path):
    write(tmp_path, "video_features_tpu/parallel/bad.py", """
        import jax

        class R:
            def drain(self, step, x, pages):
                fn = jax.jit(step, donate_argnums=(1,))
                buf = self.runner.put(x)
                for page in pages:
                    out = fn(page, buf)
    """)
    found = lint(tmp_path, "use-after-donate")
    assert len(found) == 1
    assert "donated inside a loop without being re-staged" in found[0]


def test_donate_quiet_on_loop_with_restage(tmp_path):
    write(tmp_path, "video_features_tpu/parallel/ok.py", """
        import jax

        class R:
            def drain(self, step, x, pages):
                fn = jax.jit(step, donate_argnums=(1,))
                buf = self.runner.put(x)
                for page in pages:
                    out = fn(page, buf)
                    buf = self.runner.put(out)
    """)
    assert lint(tmp_path, "use-after-donate") == []


def test_donate_pair_check_fires_when_param_not_returned(tmp_path):
    write(tmp_path, "video_features_tpu/parallel/bad.py", """
        import jax

        def paged(params, page, table):
            return params @ page

        def build():
            return jax.jit(paged, donate_argnums=(2,))
    """)
    found = lint(tmp_path, "use-after-donate")
    assert len(found) == 1
    assert "donated parameter 'table' of 'paged' is not returned" in found[0]


def test_donate_pair_check_quiet_on_passthrough(tmp_path):
    write(tmp_path, "video_features_tpu/parallel/ok.py", """
        import jax

        def paged(params, page, table):
            return params @ page, table

        def build():
            return jax.jit(paged, donate_argnums=(2,))
    """)
    assert lint(tmp_path, "use-after-donate") == []


def test_donate_annotation_suppresses(tmp_path):
    write(tmp_path, "video_features_tpu/parallel/ok.py", """
        import jax

        class R:
            def run(self, step, x):
                fn = jax.jit(step, donate_argnums=(0,))
                buf = self.runner.put(x)
                out = fn(buf)
                # use-after-donate: shape probe reads metadata, not storage
                return out, buf.shape
    """)
    assert lint(tmp_path, "use-after-donate") == []


# ---- recompile-hygiene ----------------------------------------------------


def test_recompile_fires_on_jit_in_loop(tmp_path):
    write(tmp_path, "video_features_tpu/extractors/bad.py", """
        import jax

        class E:
            def warm(self, fns):
                for fn in fns:
                    step = jax.jit(fn)
    """)
    found = lint(tmp_path, "recompile-hygiene")
    assert len(found) == 1
    assert "constructed inside a loop" in found[0]


def test_recompile_fires_on_reachable_from_extract_with_chain(tmp_path):
    """Construction two frames below extract(): the finding names the
    via-call chain through the name-based call graph."""
    write(tmp_path, "video_features_tpu/extractors/bad.py", """
        import jax

        class E:
            def extract(self, path):
                return self._build()(path)

            def _build(self):
                return jax.jit(self._fwd)
    """)
    found = lint(tmp_path, "recompile-hygiene")
    assert len(found) == 1
    assert "constructed per call" in found[0]
    assert "E.extract → E._build" in found[0]


def test_recompile_quiet_when_memoized_into_declared_table(tmp_path):
    """The _paged_fields pattern: a construction dominated by a miss on a
    declared memo table runs once per key."""
    write(tmp_path, "video_features_tpu/extractors/ok.py", """
        import jax

        class E:
            def extract(self, path):
                return self._step_for(path.depth)(path)

            def _step_for(self, key):
                cache = self.__dict__.setdefault("_paged_programs", {})
                if key not in cache:
                    step = jax.jit(self._fwd)
                    cache[key] = step
                return cache[key]
    """)
    assert lint(tmp_path, "recompile-hygiene") == []


def test_recompile_quiet_in_init_and_cached_property(tmp_path):
    write(tmp_path, "video_features_tpu/extractors/ok.py", """
        import jax
        from functools import cached_property

        class E:
            def __init__(self, fwd):
                self._step = jax.jit(fwd)

            @cached_property
            def paged(self):
                return jax.jit(self._paged_fwd)

            def extract(self, path):
                return self._step(path)
    """)
    assert lint(tmp_path, "recompile-hygiene") == []


def test_recompile_annotation_suppresses(tmp_path):
    write(tmp_path, "video_features_tpu/extractors/ok.py", """
        import jax

        class E:
            def extract(self, path):
                # recompile-hygiene: one-shot CLI path, process exits after
                step = jax.jit(self._fwd)
                return step(path)
    """)
    assert lint(tmp_path, "recompile-hygiene") == []


# ---- wire-dtype -----------------------------------------------------------


def test_wire_dtype_fires_on_float_cast_to_staging(tmp_path):
    write(tmp_path, "video_features_tpu/extractors/bad.py", """
        import numpy as np

        class E:
            def stage(self, frames):
                batch = frames.astype(np.float32)
                return self._put(batch)
    """)
    found = lint(tmp_path, "wire-dtype")
    assert len(found) == 1
    assert "float-cast value reaches staging sink" in found[0]


def test_wire_dtype_fires_through_sink_alias(tmp_path):
    """`put = self.runner.put` then `put(batch)` is still a staging sink."""
    write(tmp_path, "video_features_tpu/parallel/bad.py", """
        class P:
            def dispatch(self, frames, timed):
                put = self._put if timed else self.runner.put
                batch = frames.astype("float32")
                return put(batch)
    """)
    found = lint(tmp_path, "wire-dtype")
    assert len(found) == 1 and "staging sink" in found[0]


def test_wire_dtype_quiet_behind_declared_escape(tmp_path):
    """Both escape shapes: the `wire = f32 if cfg.float32_wire else u8`
    IfExp, and a cast lexically inside `if cfg.float32_wire:`."""
    write(tmp_path, "video_features_tpu/extractors/ok.py", """
        import numpy as np

        class E:
            def stage(self, frames):
                wire = np.float32 if self.cfg.float32_wire else np.uint8
                batch = frames.astype(wire)
                return self._put(batch)

            def stage_parity(self, frames):
                if self.cfg.float32_wire:
                    batch = frames.astype(np.float32)
                    return self._put(batch)
                return self._put(frames)
    """)
    assert lint(tmp_path, "wire-dtype") == []


def test_wire_dtype_uint8_wire_is_quiet(tmp_path):
    write(tmp_path, "video_features_tpu/extractors/ok.py", """
        import numpy as np

        class E:
            def stage(self, frames):
                batch = np.ascontiguousarray(frames.astype(np.uint8))
                return self._put(batch)
    """)
    assert lint(tmp_path, "wire-dtype") == []


def test_wire_dtype_vggish_is_exempt_wholesale(tmp_path):
    # float PCM audio wire by design — there is no uint8 wire for waveforms
    write(tmp_path, "video_features_tpu/extractors/vggish.py", """
        import numpy as np

        class V:
            def stage(self, pcm):
                return self._put(pcm.astype(np.float32))
    """)
    assert lint(tmp_path, "wire-dtype") == []


def test_wire_dtype_annotation_suppresses(tmp_path):
    write(tmp_path, "video_features_tpu/extractors/ok.py", """
        import numpy as np

        class E:
            def stage(self, frames):
                batch = frames.astype(np.float32)
                # wire-dtype: one-off fp32 calibration, not a serving path
                return self._put(batch)
    """)
    assert lint(tmp_path, "wire-dtype") == []


# ---- telemetry-schema -----------------------------------------------------

OBS_DOC = """
    ### Event catalogue

    | Event | Emitted by | Fields (beyond `ts`/`event`) |
    |---|---|---|
    | `video_done` | run loops | `video`, `model` |
    | `video_failed` | terminal accounting | `video`, `model`, `error_class` |
"""


def test_telemetry_fires_on_catalogue_missing_event(tmp_path):
    write(tmp_path, "docs/observability.md", OBS_DOC)
    write(tmp_path, "video_features_tpu/serve/s.py", """
        class S:
            def run(self, v):
                self._journal.emit("mystery_event", video=v)
    """)
    found = lint(tmp_path, "telemetry-schema")
    assert len(found) == 1
    assert "'mystery_event' is not in the docs/observability.md" in found[0]


def test_telemetry_fires_through_forwarding_wrapper(tmp_path):
    """The Extractor._emit shape: the wrapper forwards its event parameter
    and injects fields; call sites are classified through it."""
    write(tmp_path, "docs/observability.md", OBS_DOC)
    write(tmp_path, "video_features_tpu/extractors/base.py", """
        class E:
            def _emit(self, event, **fields):
                if self._journal is not None:
                    self._journal.emit(event, model=self.name, **fields)

            def extract(self, v):
                self._emit("mystery_event", video=v)
    """)
    found = lint(tmp_path, "telemetry-schema")
    assert len(found) == 1
    assert "'mystery_event'" in found[0] and "base.py:8" in found[0]


def test_telemetry_fires_on_undocumented_field(tmp_path):
    write(tmp_path, "docs/observability.md", OBS_DOC)
    write(tmp_path, "video_features_tpu/serve/s.py", """
        class S:
            def run(self, v):
                self._journal.emit("video_done", video=v, model="m",
                                   surprise=1)
    """)
    found = lint(tmp_path, "telemetry-schema")
    assert len(found) == 1
    assert "undocumented field(s) surprise" in found[0]


def test_telemetry_quiet_on_documented_events(tmp_path):
    """Literal and branch-resolved event names, documented fields only."""
    write(tmp_path, "docs/observability.md", OBS_DOC)
    write(tmp_path, "video_features_tpu/serve/s.py", """
        class S:
            def run(self, v, ok):
                name = "video_done" if ok else "video_failed"
                self._journal.emit(name, video=v, model="m")
    """)
    assert lint(tmp_path, "telemetry-schema") == []


def test_telemetry_unresolvable_event_name_is_a_finding(tmp_path):
    write(tmp_path, "docs/observability.md", OBS_DOC)
    write(tmp_path, "video_features_tpu/serve/s.py", """
        class S:
            def run(self):
                self._journal.emit(self.event_name, video=1)
    """)
    found = lint(tmp_path, "telemetry-schema")
    assert len(found) == 1
    assert "not statically resolvable" in found[0]


def test_telemetry_stats_schema_two_way(tmp_path):
    write(tmp_path, "docs/serving.md", """
        ## The `stats` payload (schema 1)

        | Field | Meaning |
        |---|---|
        | `ok`, `schema` | op success; payload version |
        | `packing.{real_slots}` | packer totals |
        | `ghost` | documented but never emitted |
    """)
    write(tmp_path, "video_features_tpu/serve/daemon.py", """
        class S:
            def stats(self):
                return {
                    "ok": True,
                    "schema": 1,
                    "packing": {"real_slots": 1, "occupancy": 0.5},
                    "extra_top": 2,
                }
    """)
    found = lint(tmp_path, "telemetry-schema")
    assert any("undocumented top-level field 'extra_top'" in f
               for f in found)
    assert any("'packing.occupancy' is not in the" in f for f in found)
    assert any("documents 'ghost' but the stats op no longer emits"
               in f for f in found)
    assert len(found) == 3


def test_telemetry_stats_quiet_when_documented(tmp_path):
    write(tmp_path, "docs/serving.md", """
        ## The `stats` payload (schema 1)

        | Field | Meaning |
        |---|---|
        | `ok`, `schema` | op success; payload version |
        | `packing.{real_slots, occupancy}` | packer totals |
        | `tenants.<name>.{pending}` | not enumerable: wildcard subs |
    """)
    write(tmp_path, "video_features_tpu/serve/daemon.py", """
        class S:
            def stats(self):
                return {
                    "ok": True,
                    "schema": 1,
                    "packing": {"real_slots": 1, "occupancy": 0.5},
                    "tenants": self.queue.stats(),
                }
    """)
    assert lint(tmp_path, "telemetry-schema") == []


def test_telemetry_annotation_suppresses(tmp_path):
    write(tmp_path, "docs/observability.md", OBS_DOC)
    write(tmp_path, "video_features_tpu/serve/s.py", """
        class S:
            def run(self, v):
                # telemetry-schema: staging-only probe, stripped pre-release
                self._journal.emit("probe_event", video=v)
    """)
    assert lint(tmp_path, "telemetry-schema") == []


# ---- stale-suppression reconciliation -------------------------------------


def test_stale_suppression_is_flagged(tmp_path):
    """An annotation nothing consumed this run is dead weight — the same
    reconciliation stale lock declarations get."""
    write(tmp_path, "video_features_tpu/models/m.py", """
        import jax.numpy as jnp
        # explicit-dtype: promotion wanted (the violation is long gone)
        x = jnp.zeros((2,), dtype=jnp.float32)
    """)
    found = lint(tmp_path, "explicit-dtype")
    assert len(found) == 1
    assert "stale '# explicit-dtype:' suppression" in found[0]


def test_live_suppression_is_not_stale(tmp_path):
    # consumed by the rule → no stale finding, no violation finding
    write(tmp_path, "video_features_tpu/models/m.py", """
        import jax.numpy as jnp
        # explicit-dtype: promotion wanted here
        x = jnp.asarray([1.0])
    """)
    assert lint(tmp_path, "explicit-dtype") == []


def test_fast_registry_comment_outside_default_tier_is_stale(
        tmp_path, monkeypatch):
    """fast-registry's grammar is file-level (annotation_live override):
    the comment is live only while the module sits in DEFAULT_TIER."""
    monkeypatch.setattr(fast_registry, "DEFAULT_TIER", {})
    _tiered_tree(tmp_path)
    write(tmp_path, "tests/test_a.py",
          "# fast-registry: left over from a previous tier\n"
          "def test_x():\n    pass\n")
    found = lint(tmp_path, "fast-registry")
    assert len(found) == 1 and "stale" in found[0]


# ---- --changed / --suppressions -------------------------------------------


def test_cli_changed_mode_reports_only_the_diff(tmp_path, capsys):
    import subprocess

    def git(*args):
        subprocess.run(["git", "-C", str(tmp_path), *args],
                       check=True, capture_output=True)

    # committed baseline has a violation; the new (untracked) file has
    # another — --changed --base HEAD reports only the new one
    write(tmp_path, "video_features_tpu/models/old.py",
          "import jax.numpy as jnp\nx = jnp.asarray([1.0])\n")
    git("init", "-q")
    git("add", "-A")
    git("-c", "user.email=t@t", "-c", "user.name=t", "commit", "-qm", "base")
    write(tmp_path, "video_features_tpu/models/new.py",
          "import jax.numpy as jnp\ny = jnp.arange(3)\n")
    assert vftlint_main(["--changed", "--base", "HEAD", str(tmp_path)]) == 1
    out = capsys.readouterr().out
    assert "new.py" in out and "old.py" not in out


def test_cli_changed_mode_clean_when_no_diff(tmp_path, capsys):
    import subprocess

    def git(*args):
        subprocess.run(["git", "-C", str(tmp_path), *args],
                       check=True, capture_output=True)

    write(tmp_path, "video_features_tpu/models/old.py",
          "import jax.numpy as jnp\nx = jnp.asarray([1.0])\n")
    git("init", "-q")
    git("add", "-A")
    git("-c", "user.email=t@t", "-c", "user.name=t", "commit", "-qm", "base")
    assert vftlint_main(["--changed", "--base", "HEAD", str(tmp_path)]) == 0
    assert "no files changed" in capsys.readouterr().out


def test_cli_changed_outside_git_lints_everything(tmp_path, capsys):
    write(tmp_path, "video_features_tpu/models/m.py",
          "import jax.numpy as jnp\nx = jnp.asarray([1.0])\n")
    assert vftlint_main(["--changed", str(tmp_path)]) == 1
    err = capsys.readouterr().err
    assert "needs a git checkout" in err


def test_cli_suppressions_lists_annotations(tmp_path, capsys):
    write(tmp_path, "video_features_tpu/models/m.py", """
        import jax.numpy as jnp
        # explicit-dtype: promotion deliberate here
        x = jnp.asarray([1.0])
    """)
    assert vftlint_main(["--suppressions", str(tmp_path)]) == 0
    out = capsys.readouterr().out
    assert ("video_features_tpu/models/m.py:3 explicit-dtype "
            "promotion deliberate here") in out


def test_suppression_ledger_matches_docs():
    """The (file, rule, count) ledger in docs/static-analysis.md mirrors
    `--suppressions` exactly — adding or removing an annotation without
    updating the ledger fails here."""
    from tools.vftlint.core import collect_suppressions

    counts = {}
    for rel, _line, rule, _reason in collect_suppressions(REPO):
        counts[(rel, rule)] = counts.get((rel, rule), 0) + 1

    doc = open(os.path.join(REPO, "docs", "static-analysis.md"),
               encoding="utf-8").read()
    assert "### Suppression ledger" in doc
    section = doc.split("### Suppression ledger", 1)[1]
    section = section.split("\n## ")[0].split("\n### ")[0]
    documented = {}
    for line in section.splitlines():
        if not line.startswith("|") or set(line) <= {"|", "-", " "}:
            continue
        cells = [c.strip().strip("`") for c in line.strip("|").split("|")]
        if len(cells) >= 3 and cells[2].isdigit():
            documented[(cells[0], cells[1])] = int(cells[2])
    assert documented == counts


# ---- LockOrderWatch (runtime cross-check shim) -----------------------------


def test_lock_order_watch_records_edges_and_violations():
    import threading

    watch = LockOrderWatch(["a", "b"])
    la = watch.wrap(threading.Lock(), "a")
    lb = watch.wrap(threading.Lock(), "b")
    with la:
        with lb:
            pass
    assert ("a", "b") in watch.edges and watch.violations == []
    watch.assert_clean()
    with lb:
        with la:
            pass
    assert len(watch.violations) == 1
    assert "'a' while holding 'b'" in watch.violations[0]
    with pytest.raises(AssertionError):
        watch.assert_clean()


def test_lock_order_watch_rlock_reentry_is_not_an_edge():
    import threading

    watch = LockOrderWatch(["a"])
    la = watch.wrap(threading.RLock(), "a")
    with la:
        with la:
            pass
    assert watch.edges == set() and watch.violations == []


# ---- parse-once budget ----------------------------------------------------


def test_sources_parsed_once_per_run(monkeypatch):
    """9+ rules must not re-parse per rule: each file is constructed into a
    SourceFile exactly once per run_lint call."""
    import tools.vftlint.core as core

    counts = {}
    orig = core.SourceFile.__init__

    def counting(self, root, rel):
        counts[rel] = counts.get(rel, 0) + 1
        orig(self, root, rel)

    monkeypatch.setattr(core.SourceFile, "__init__", counting)
    assert run_lint(REPO) == []
    assert counts, "no sources scanned?"
    multi = {rel: n for rel, n in counts.items() if n != 1}
    assert multi == {}, f"re-parsed per rule: {multi}"


def test_full_run_work_budget():
    """The full 13-rule suite stays within ~25% over the measured baseline of
    its work, counted as Python function calls (12.3 M on the tree of PR 37)
    — the budget guards against O(files x rules) parse regressions and against
    a new interprocedural pass quietly re-deriving the shared analyses, not
    against small constant cost. A count, not a clock: the same lint read
    2.9–4.8 s on one otherwise idle machine, and the 4.5 s best-of-three pin
    this replaces failed under the driver's six workers."""
    calls = 0

    def count(_frame, event, _arg):
        nonlocal calls
        if event == "call":
            calls += 1

    previous = sys.getprofile()
    sys.setprofile(count)
    try:
        findings = run_lint(REPO)
    finally:
        sys.setprofile(previous)
    assert findings == []
    assert calls < 15_500_000


def test_changed_mode_single_file_is_fast(monkeypatch):
    """--changed on a one-file diff stays a pre-commit-speed loop: the tree
    is still parsed and prepare()d (the interprocedural rules need it), but
    per-file checks run only on the diff. Counted, not timed: a wall-clock
    pin under a loaded full-suite run measures contention, not the lint."""
    changed = "video_features_tpu/serve/wal.py"
    checked = []
    for rule in all_rules().values():
        def counting(src, _orig=rule.check_file):
            checked.append(src.rel)
            return _orig(src)

        monkeypatch.setattr(rule, "check_file", counting)
    assert run_lint(REPO, only={changed}) == []
    wanting = sum(rule.wants(changed) for rule in all_rules().values()
                  if any(changed.startswith(root) for root in rule.roots))
    assert set(checked) == {changed} and 0 < len(checked) <= wanting


# ---- --format json / github ------------------------------------------------


def test_cli_json_format(tmp_path, capsys):
    import json

    write(tmp_path, "video_features_tpu/models/m.py",
          "import jax.numpy as jnp\nx = jnp.asarray([1.0])\n")
    assert vftlint_main(["--format", "json", str(tmp_path)]) == 1
    data = json.loads(capsys.readouterr().out)
    assert len(data) == 1
    rec = data[0]
    assert rec["file"] == "video_features_tpu/models/m.py"
    assert rec["line"] == 2 and rec["rule"] == "explicit-dtype"
    assert "dtype" in rec["message"]
    assert rec["suppression"] == "# explicit-dtype: <reason>"


def test_cli_json_clean_is_empty_array(capsys):
    import json

    assert vftlint_main(["--format", "json", REPO]) == 0
    assert json.loads(capsys.readouterr().out) == []


def test_cli_github_format(tmp_path, capsys):
    write(tmp_path, "video_features_tpu/models/m.py",
          "import jax.numpy as jnp\nx = jnp.asarray([1.0])\n")
    assert vftlint_main(["--format", "github", str(tmp_path)]) == 1
    out = capsys.readouterr().out
    assert out.startswith("::error file=video_features_tpu/models/m.py,"
                          "line=2,title=vftlint explicit-dtype::")


# ---- framework ------------------------------------------------------------


def test_parse_error_is_reported_once(tmp_path):
    write(tmp_path, "video_features_tpu/broken.py", "def f(:\n")
    findings = run_lint(str(tmp_path))
    parse = [f for f in findings if f.rule == "parse-error"]
    assert len(parse) == 1


def test_findings_format():
    from tools.vftlint import Finding

    f = Finding("pkg/mod.py", 7, "host-sync", "boom")
    assert str(f) == "pkg/mod.py:7 host-sync boom"
    assert str(Finding("pkg/mod.py", 0, "r", "m")) == "pkg/mod.py r m"


@pytest.mark.parametrize("rule_id", sorted(ALL_RULE_IDS))
def test_each_rule_runs_standalone_on_repo(rule_id):
    assert run_lint(REPO, [rule_id]) == []

"""rtlint — the concurrency & invariant analyzer — and its runtime
lock-order complement.

Three layers:

1. fixture snippets: each rule both FIRES on a violating snippet and
   stays QUIET on the corrected twin (the analyzer's contract);
2. the live package: `ray_tpu lint` must be green (real fixes +
   explicit baseline only) and the static lock digraph acyclic;
3. the dynamic mode: the instrumented lock wrapper observes real
   acquisition order and the cycle check works both ways.

All of this is tier-1: pure AST + threads, no cluster, no JAX.
"""

import json
import os
import subprocess
import sys
import textwrap
import threading
import time

import pytest

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO_ROOT) if REPO_ROOT not in sys.path else None

from tools.rtlint import analyzer, baseline as baseline_mod  # noqa: E402
from tools.rtlint import rules_knobs  # noqa: E402


# -- fixture harness ---------------------------------------------------------

CONFIG_STUB = '''
_CONFIG_DEFS = {
    "used_knob": (int, 1, "a documented, referenced knob"),
    "dead_knob": (int, 2, "defined but never read"),
    "undocumented_knob": (bool, False, ""),
}

def get_config():
    return None
'''


def lint_snippet(tmp_path, source, rules=("W1", "W2", "W3", "W4"),
                 config_defs=CONFIG_STUB):
    """Run the analyzer over one module + a config stub, as a package."""
    pkg = tmp_path / "fixturepkg"
    (pkg / "common").mkdir(parents=True)
    (pkg / "common" / "config.py").write_text(config_defs)
    (pkg / "mod.py").write_text(textwrap.dedent(source))
    findings = analyzer.run_analysis(str(tmp_path), package="fixturepkg",
                                     rules=rules)
    return [f for f in findings if f.rule != "E0"]


def details(findings):
    return [(f.rule, f.detail or f.message) for f in findings]


# -- W1: blocking-call-under-lock -------------------------------------------

class TestW1:
    def test_fires_on_sleep_rpc_join_socket_under_lock(self, tmp_path):
        fs = lint_snippet(tmp_path, '''
            import threading, time

            class Svc:
                def __init__(self, client, sock, thread):
                    self._lock = threading.Lock()
                    self.client = client
                    self.sock = sock
                    self.reader_thread = thread

                def bad_sleep(self):
                    with self._lock:
                        time.sleep(1.0)

                def bad_rpc(self):
                    with self._lock:
                        return self.client.call("stats")

                def bad_result(self):
                    with self._lock:
                        return self.client.call_async("stats").result(5)

                def bad_join(self):
                    with self._lock:
                        self.reader_thread.join(2.0)

                def bad_socket(self):
                    with self._lock:
                        return self.sock.recv(4096)
            ''', rules=("W1",))
        kinds = {d for _, d in details(fs)}
        assert any("time.sleep@" in d for d in kinds), kinds
        assert any(".call@" in d for d in kinds), kinds
        assert any(".result" in d for d in kinds), kinds
        assert any(".join@" in d for d in kinds), kinds
        assert any(".recv@" in d for d in kinds), kinds
        assert len(fs) == 5

    def test_quiet_when_blocking_moved_outside(self, tmp_path):
        fs = lint_snippet(tmp_path, '''
            import threading, time

            class Svc:
                def __init__(self, client):
                    self._lock = threading.Lock()
                    self.client = client
                    self.pending = []

                def good(self):
                    with self._lock:
                        batch = list(self.pending)
                        self.pending.clear()
                    # blocking work AFTER the critical section
                    time.sleep(0.01)
                    return self.client.call("flush", batch)
            ''', rules=("W1",))
        assert fs == []

    def test_cv_wait_idiom_is_quiet_but_foreign_wait_fires(self, tmp_path):
        fs = lint_snippet(tmp_path, '''
            import threading

            class Store:
                def __init__(self, event):
                    self._lock = threading.Lock()
                    self._cv = threading.Condition(self._lock)
                    self._ev = event

                def good_wait(self):
                    with self._cv:
                        self._cv.wait(1.0)

                def good_alias_wait(self):
                    # Condition wraps _lock: waiting RELEASES the lock
                    with self._lock:
                        self._cv.wait(1.0)

                def bad_event_wait(self):
                    with self._lock:
                        self._ev.wait(1.0)
            ''', rules=("W1",))
        ds = details(fs)
        assert len(fs) == 1, ds
        assert "._ev.wait" in ds[0][1]

    def test_interprocedural_one_level(self, tmp_path):
        fs = lint_snippet(tmp_path, '''
            import threading, time

            class Svc:
                def __init__(self):
                    self._lock = threading.Lock()

                def _slow_helper(self):
                    time.sleep(0.5)

                def bad(self):
                    with self._lock:
                        self._slow_helper()
            ''', rules=("W1",))
        assert len(fs) == 1
        assert "via-_slow_helper" in fs[0].detail

    def test_closure_under_lock_is_deferred_not_flagged(self, tmp_path):
        fs = lint_snippet(tmp_path, '''
            import threading, time

            class Svc:
                def __init__(self):
                    self._lock = threading.Lock()

                def ok(self):
                    with self._lock:
                        cb = lambda: time.sleep(1.0)
                        def later():
                            time.sleep(2.0)
                    return cb, later
            ''', rules=("W1",))
        assert fs == []

    def test_inline_suppression(self, tmp_path):
        fs = lint_snippet(tmp_path, '''
            import threading, time

            class Svc:
                def __init__(self):
                    self._wlock = threading.Lock()

                def serialized_write(self, sock, frame):
                    with self._wlock:
                        sock.sendall(frame)    # rtlint: disable=W1
            ''', rules=("W1",))
        assert fs == []


# -- W2: lock-order cycles ---------------------------------------------------

class TestW2:
    def test_fires_on_ab_ba_cycle_with_witnesses(self, tmp_path):
        fs = lint_snippet(tmp_path, '''
            import threading

            class Svc:
                def __init__(self):
                    self._a_lock = threading.Lock()
                    self._b_lock = threading.Lock()

                def path_one(self):
                    with self._a_lock:
                        with self._b_lock:
                            return 1

                def path_two(self):
                    with self._b_lock:
                        with self._a_lock:
                            return 2
            ''', rules=("W2",))
        assert len(fs) == 1
        msg = fs[0].message
        assert "lock-order cycle" in msg
        # both witness paths printed
        assert "path_one" in msg and "path_two" in msg
        assert "Svc._a_lock" in msg and "Svc._b_lock" in msg

    def test_quiet_on_consistent_order(self, tmp_path):
        fs = lint_snippet(tmp_path, '''
            import threading

            class Svc:
                def __init__(self):
                    self._a_lock = threading.Lock()
                    self._b_lock = threading.Lock()

                def path_one(self):
                    with self._a_lock:
                        with self._b_lock:
                            return 1

                def path_two(self):
                    with self._a_lock:
                        with self._b_lock:
                            return 2
            ''', rules=("W2",))
        assert fs == []

    def test_cycle_through_method_call(self, tmp_path):
        fs = lint_snippet(tmp_path, '''
            import threading

            class Svc:
                def __init__(self):
                    self._a_lock = threading.Lock()
                    self._b_lock = threading.Lock()

                def _takes_a(self):
                    with self._a_lock:
                        return 0

                def path_one(self):
                    with self._a_lock:
                        with self._b_lock:
                            return 1

                def path_two(self):
                    with self._b_lock:
                        return self._takes_a()
            ''', rules=("W2",))
        assert len(fs) == 1
        assert "via self._takes_a()" in fs[0].message


# -- W3: config-knob discipline ---------------------------------------------

class TestW3:
    SOURCE = '''
        from .common.config import get_config

        def reads():
            cfg = get_config()
            a = cfg.used_knob
            b = cfg.typo_knob
            c = getattr(cfg, "undocumented_knob", None)
            d = get_config().another_typo
            return a, b, c, d
        '''

    def test_unknown_unused_and_empty_doc(self, tmp_path):
        ds = details(lint_snippet(tmp_path, self.SOURCE, rules=("W3",)))
        assert ("W3", "unknown-knob:typo_knob") in ds
        assert ("W3", "unknown-knob:another_typo") in ds
        assert ("W3", "unused-knob:dead_knob") in ds
        assert ("W3", "empty-doc:undocumented_knob") in ds
        # used_knob is referenced + documented: nothing else fires
        assert len(ds) == 4

    def test_string_literal_counts_as_reference(self, tmp_path):
        fs = lint_snippet(tmp_path, '''
            def dynamic():
                # a to_dict()-driven consumer names the knob as a string
                return ["dead_knob", "used_knob", "undocumented_knob"]
            ''', rules=("W3",))
        assert not any("unused-knob" in d for _, d in details(fs))

    def test_live_defs_parse(self):
        defs = rules_knobs.load_defs(
            os.path.join(REPO_ROOT, "ray_tpu", "common", "config.py"))
        assert "scheduler_spread_threshold" in defs
        assert "rtlint_runtime_lock_order" in defs
        assert all(info["doc"].strip() for info in defs.values()), \
            "every live knob must carry a doc string"


# -- W4: thread lifecycle ----------------------------------------------------

class TestW4:
    def test_non_daemon_unjoined_fires(self, tmp_path):
        fs = lint_snippet(tmp_path, '''
            import threading

            def fire_and_forget(fn):
                threading.Thread(target=fn).start()
            ''', rules=("W4",))
        assert len(fs) == 1
        assert "non-daemon" in fs[0].detail

    def test_daemon_or_joined_is_quiet(self, tmp_path):
        fs = lint_snippet(tmp_path, '''
            import threading

            class Svc:
                def start(self, fn):
                    self._t = threading.Thread(target=fn)
                    self._t.start()
                def stop(self):
                    self._t.join(5.0)

            def ok(fn):
                threading.Thread(target=fn, daemon=True).start()
            ''', rules=("W4",))
        assert fs == []

    def test_silent_pump_swallow_fires(self, tmp_path):
        fs = lint_snippet(tmp_path, '''
            import threading

            class Pump:
                def start(self):
                    threading.Thread(target=self._loop, daemon=True).start()

                def _loop(self):
                    while True:
                        try:
                            self._step()
                        except Exception:
                            pass
            ''', rules=("W4",))
        assert len(fs) == 1
        assert "swallow" in fs[0].detail

    def test_logged_handler_is_quiet_and_bare_except_fires(self, tmp_path):
        fs = lint_snippet(tmp_path, '''
            import logging, threading

            class Pump:
                def start(self):
                    threading.Thread(target=self._loop, daemon=True).start()
                    threading.Thread(target=self._bad, daemon=True).start()

                def _loop(self):
                    while True:
                        try:
                            self._step()
                        except Exception:
                            logging.getLogger(__name__).debug(
                                "step failed", exc_info=True)

                def _bad(self):
                    try:
                        self._step()
                    except:
                        pass
            ''', rules=("W4",))
        ds = details(fs)
        assert len(fs) == 1, ds
        assert "swallow:bare" in fs[0].detail

    def test_specific_exception_pass_is_quiet(self, tmp_path):
        fs = lint_snippet(tmp_path, '''
            import threading

            class Pump:
                def start(self):
                    threading.Thread(target=self._loop, daemon=True).start()

                def _loop(self):
                    while True:
                        try:
                            self._step()
                        except (EOFError, OSError):
                            break
            ''', rules=("W4",))
        assert fs == []


# -- the live package --------------------------------------------------------

class TestLivePackage:
    def test_lint_green_over_package(self):
        """The acceptance gate: real fixes + explicit baseline only."""
        new, based, stale, _ = analyzer.check(
            REPO_ROOT, "ray_tpu",
            baseline_path=os.path.join(REPO_ROOT, "tools", "rtlint",
                                       "baseline.json"))
        assert new == [], "non-baselined findings:\n" + "\n".join(
            f.format_text() for f in new)
        assert stale == [], f"stale baseline entries: {stale}"

    def test_static_lock_graph_acyclic_and_nonempty(self):
        adj = analyzer.lock_graph(REPO_ROOT)
        assert sum(len(v) for v in adj.values()) >= 3, \
            "lock graph suspiciously empty — detection broken?"
        from tools.rtlint import rules_locks
        assert rules_locks.find_cycles(adj) == []

    def test_cli_json_gate(self):
        """`ray_tpu lint --format=json` is the CI gate: exit 0 + valid
        JSON while green."""
        proc = subprocess.run(
            [sys.executable, "-m", "tools.rtlint", "--format=json",
             f"--root={REPO_ROOT}"],
            capture_output=True, text=True, timeout=120)
        assert proc.returncode == 0, proc.stdout + proc.stderr
        report = json.loads(proc.stdout)
        assert report["counts"]["new"] == 0
        assert report["counts"]["baselined"] >= 1

    def test_cli_nonzero_on_new_findings(self, tmp_path):
        """Without the baseline the same run must exit 1 — proving the
        gate actually gates."""
        proc = subprocess.run(
            [sys.executable, "-m", "tools.rtlint", "--format=json",
             "--no-baseline", f"--root={REPO_ROOT}"],
            capture_output=True, text=True, timeout=120)
        assert proc.returncode == 1
        report = json.loads(proc.stdout)
        assert report["counts"]["new"] >= 1


# -- baseline ratchet --------------------------------------------------------

class TestBaseline:
    def test_update_round_trips_deterministically(self, tmp_path):
        findings = analyzer.run_analysis(REPO_ROOT, "ray_tpu")
        p1, p2 = tmp_path / "b1.json", tmp_path / "b2.json"
        baseline_mod.save(str(p1), findings)
        baseline_mod.save(str(p2), list(reversed(findings)))
        assert p1.read_bytes() == p2.read_bytes(), \
            "--update-baseline must be input-order independent"
        # keys sorted
        doc = json.loads(p1.read_text())
        keys = list(doc["findings"])
        assert keys == sorted(keys)
        # and loading back suppresses exactly those findings
        accepted = baseline_mod.load(str(p1))
        new, based, stale = baseline_mod.split(findings, accepted)
        assert new == [] and stale == []
        assert len(based) == len(findings)

    def test_checked_in_baseline_matches_regeneration(self):
        """The checked-in file IS what --update-baseline emits today."""
        findings = analyzer.run_analysis(REPO_ROOT, "ray_tpu")
        on_disk = open(os.path.join(
            REPO_ROOT, "tools", "rtlint", "baseline.json")).read()
        assert on_disk == baseline_mod.render(findings)

    def test_fingerprint_survives_line_drift(self, tmp_path):
        src = '''
            import threading, time

            class Svc:
                def __init__(self):
                    self._lock = threading.Lock()

                def bad(self):
                    with self._lock:
                        time.sleep(1.0)
            '''
        f1 = lint_snippet(tmp_path / "a", src, rules=("W1",))
        # blank lines shift every statement down without altering indent
        f2 = lint_snippet(tmp_path / "b", "\n\n\n" + src, rules=("W1",))
        assert f1[0].fingerprint == f2[0].fingerprint
        assert f1[0].line != f2[0].line


# -- runtime lock-order mode -------------------------------------------------

class TestRuntimeLockOrder:
    @pytest.fixture(autouse=True)
    def _clean(self):
        from ray_tpu.common import lockorder
        was = lockorder.installed()
        yield
        if not was:
            lockorder.uninstall()
        lockorder.reset()

    def test_config_gate(self):
        from ray_tpu.common import lockorder
        from ray_tpu.common.config import Config
        if lockorder.installed():
            pytest.skip("suite already runs with the recorder installed")
        Config.reset()
        assert lockorder.maybe_install_from_config() is False
        Config.reset(system_config={"rtlint_runtime_lock_order": True})
        assert lockorder.maybe_install_from_config() is True
        assert lockorder.installed()

    def test_observes_real_nesting_and_detects_inversion(self):
        from ray_tpu.common import lockorder
        lockorder.install()
        lockorder.reset()
        # separate lines: lock identity is the allocation site, and two
        # locks born on one line would collapse into a single node
        a = threading.Lock()
        b = threading.Lock()
        with a:
            with b:
                pass
        assert lockorder.find_cycle() is None
        assert len(lockorder.edges()) == 1
        with b:
            with a:
                pass
        cyc = lockorder.find_cycle()
        assert cyc is not None
        with pytest.raises(AssertionError, match="lock-order cycle"):
            lockorder.assert_acyclic()

    def test_condition_wait_does_not_leak_held_state(self):
        """cv.wait() releases the lock: acquisitions made by OTHER
        threads while we wait must not edge off our lock."""
        from ray_tpu.common import lockorder
        lockorder.install()
        lockorder.reset()
        lk = threading.Lock()
        cv = threading.Condition(lk)
        other = threading.Lock()
        hits = []

        def side():
            # runs while main waits; held-stack of THIS thread is empty
            with other:
                hits.append(1)
            with cv:
                cv.notify_all()

        t = threading.Thread(target=side, daemon=True)
        with cv:
            t.start()
            cv.wait(2.0)
        t.join(2.0)
        assert hits == [1]
        # after the wait round-trip our thread can nest again cleanly
        with other:
            pass
        assert lockorder.find_cycle() is None

    def test_rlock_reentry_records_nothing(self):
        from ray_tpu.common import lockorder
        lockorder.install()
        lockorder.reset()
        rl = threading.RLock()
        with rl:
            with rl:
                pass
        assert lockorder.edges() == {}
        assert lockorder.self_edges() == {}

    def test_multithreaded_runtime_workload_stays_acyclic(self):
        """A miniature of what the chaos suite exercises: many threads
        hammering nested-lock structures in one consistent order."""
        from ray_tpu.common import lockorder
        lockorder.install()
        lockorder.reset()

        class Account:
            def __init__(self):
                self.lock = threading.Lock()
                self.bal = 0

        ledger_lock = threading.Lock()
        accounts = [Account() for _ in range(4)]

        def worker(seed):
            for i in range(50):
                acct = accounts[(seed + i) % len(accounts)]
                with ledger_lock:       # global before per-account
                    with acct.lock:
                        acct.bal += 1
                time.sleep(0)

        ts = [threading.Thread(target=worker, args=(k,), daemon=True)
              for k in range(8)]
        [t.start() for t in ts]
        [t.join(10.0) for t in ts]
        assert sum(a.bal for a in accounts) == 8 * 50
        assert lockorder.find_cycle() is None
        lockorder.assert_acyclic()
        # the ledger->account ordering was actually observed
        assert any("ledger" not in a and "ledger" not in b or True
                   for (a, b) in lockorder.edges())
        assert len(lockorder.edges()) >= 1


# -- W5: clock/transport seam discipline -------------------------------------

class TestW5:
    def _lint(self, tmp_path, relpath, source):
        """W5 scopes by real package paths, so fixtures are written
        under a throwaway ``ray_tpu/`` tree."""
        target = tmp_path / relpath
        target.parent.mkdir(parents=True, exist_ok=True)
        target.write_text(textwrap.dedent(source))
        findings = analyzer.run_analysis(
            str(tmp_path), package="ray_tpu", rules=("W5",),
            files=[str(target)])
        return [f for f in findings if f.rule != "E0"]

    def test_fires_on_direct_time_in_runtime(self, tmp_path):
        fs = self._lint(tmp_path, "ray_tpu/runtime/mod.py", '''
            import time
            import time as _time
            from time import sleep

            def deadline():
                return time.monotonic() + 5.0

            def stamp():
                return _time.time()

            def pause():
                sleep(0.1)

            def legal():
                return time.perf_counter()
            ''')
        details = sorted(f.detail for f in fs)
        assert len(fs) == 3, details
        assert any(d.startswith("clock:monotonic@deadline")
                   for d in details), details
        assert any(d.startswith("clock:time@stamp") for d in details)
        assert any(d.startswith("clock:sleep@pause") for d in details)

    def test_fires_on_direct_rpc_ctor_in_runtime(self, tmp_path):
        fs = self._lint(tmp_path, "ray_tpu/runtime/mod.py", '''
            from ..rpc.client import RpcClient
            from ..rpc.server import RpcServer

            def make(addr):
                c = RpcClient(addr)
                s = RpcServer({})
                return c, s
            ''')
        details = sorted(f.detail for f in fs)
        assert len(fs) == 2, details
        assert "transport:RpcClient@make" in details
        assert "transport:RpcServer@make" in details

    def test_quiet_when_routed_through_seams(self, tmp_path):
        fs = self._lint(tmp_path, "ray_tpu/runtime/mod.py", '''
            from ..common import clock as _clk
            from ..rpc import transport as _transport

            def deadline():
                return _clk.monotonic() + 5.0

            def make(addr):
                return _transport.connect(addr)
            ''')
        assert fs == []

    def test_out_of_scope_and_suppressed_sites_quiet(self, tmp_path):
        # outside runtime//rpc/: free to use wall time
        fs = self._lint(tmp_path, "ray_tpu/serve/mod.py", '''
            import time

            def stamp():
                return time.time()
            ''')
        assert fs == []
        # rpc/ ctor use is the transport's own implementation detail
        fs = self._lint(tmp_path, "ray_tpu/rpc/mod.py", '''
            def make(addr):
                return RpcClient(addr)
            ''')
        assert fs == []
        # deliberate wall-clock site, visibly annotated
        fs = self._lint(tmp_path, "ray_tpu/runtime/mod.py", '''
            import time

            def stamp():
                return time.time()  # rtlint: disable=W5
            ''')
        assert fs == []

    def test_live_package_w5_is_baselined_only(self):
        """The seam audit itself: no NEW control-plane code bypasses
        the clock/transport seams (worker-subprocess sites are the
        explicit baseline)."""
        new, based, stale, _ = analyzer.check(
            REPO_ROOT, "ray_tpu", rules=("W5",),
            baseline_path=os.path.join(REPO_ROOT, "tools", "rtlint",
                                       "baseline.json"))
        assert new == [], [f.format_text() for f in new]
        assert all(f.path.endswith("runtime/worker.py") for f in based)

# -- W6: heartbeat host<->device sync discipline ------------------------------

class TestW6:
    def _lint(self, tmp_path, relpath, source):
        """W6 scopes by real package paths (ops/, scheduling/,
        runtime/raylet.py), so fixtures mirror that tree."""
        target = tmp_path / relpath
        target.parent.mkdir(parents=True, exist_ok=True)
        target.write_text(textwrap.dedent(source))
        findings = analyzer.run_analysis(
            str(tmp_path), package="ray_tpu", rules=("W6",),
            files=[str(target)])
        return [f for f in findings if f.rule != "E0"]

    def test_fires_on_explicit_syncs(self, tmp_path):
        fs = self._lint(tmp_path, "ray_tpu/ops/mod.py", '''
            import jax
            from jax import device_get

            def fetch(x):
                return jax.device_get(x)

            def fetch2(x):
                return device_get(x)

            def stall(x):
                x.block_until_ready()
                return x
            ''')
        details = sorted(f.detail for f in fs)
        assert len(fs) == 3, details
        assert "sync:device_get@fetch" in details
        assert "sync:device_get@fetch2" in details
        assert "sync:block_until_ready@stall" in details

    def test_fires_on_np_coercion_only_in_jax_functions(self, tmp_path):
        fs = self._lint(tmp_path, "ray_tpu/scheduling/mod.py", '''
            import numpy as np

            def device_beat(x):
                import jax
                y = jax.device_put(x)
                return np.asarray(y)        # implicit sync

            def host_only(v):
                return np.asarray(v)        # plain numpy: legal
            ''')
        details = sorted(f.detail for f in fs)
        assert len(fs) == 1, details
        assert "sync:asarray@device_beat" in details

    def test_out_of_scope_and_suppressed_sites_quiet(self, tmp_path):
        # outside ops//scheduling//raylet: free to sync
        fs = self._lint(tmp_path, "ray_tpu/serve/mod.py", '''
            import jax

            def fetch(x):
                return jax.device_get(x)
            ''')
        assert fs == []
        # the sanctioned per-beat readback, visibly annotated
        fs = self._lint(tmp_path, "ray_tpu/ops/mod.py", '''
            import jax
            import numpy as np

            def beat(x):
                y = jax.device_put(x)
                return np.asarray(y)  # rtlint: disable=W6
            ''')
        assert fs == []

    def test_live_heartbeat_path_w6_is_baselined_only(self):
        """The data-path audit itself: every host<->device sync in the
        live heartbeat path is a known, deliberate readback site."""
        new, based, stale, _ = analyzer.check(
            REPO_ROOT, "ray_tpu", rules=("W6",),
            baseline_path=os.path.join(REPO_ROOT, "tools", "rtlint",
                                       "baseline.json"))
        assert new == [], [f.format_text() for f in new]
        assert based, "expected the sanctioned readback sites"

    def test_new_knobs_pass_w3(self):
        """The r08 knob (scheduler_delta_max_dirty_fraction) and the
        r14 knobs
        (scheduler_shards, scheduler_shard_reduce) are documented and
        referenced — W3 stays clean on the live package."""
        new, _based, _stale, _ = analyzer.check(
            REPO_ROOT, "ray_tpu", rules=("W3",),
            baseline_path=os.path.join(REPO_ROOT, "tools", "rtlint",
                                       "baseline.json"))
        assert new == [], [f.format_text() for f in new]

    def test_sharded_beat_modules_in_scope_with_zero_baseline(self):
        """The r14 shard-reduce plane is inside W6's scope (its paths
        match the ops//scheduling/ prefixes) AND contributes zero
        baseline entries: every sanctioned sync in the new modules is
        inline-annotated, none is grandfathered."""
        from tools.rtlint import rules_device
        new_modules = ("ray_tpu/ops/shard_reduce.py",
                       "ray_tpu/scheduling/sharded_delta.py")
        for mod in new_modules:
            assert os.path.exists(os.path.join(REPO_ROOT, mod))
            assert any(mod.startswith(sc) for sc in rules_device._SCOPES)
        accepted = baseline_mod.load(os.path.join(
            REPO_ROOT, "tools", "rtlint", "baseline.json"))
        for key in accepted:
            assert not any(m in key for m in new_modules), \
                f"grandfathered finding in a new module: {key}"
        # and the scope is live, not vacuous: a sync planted in the
        # module's path fires
        findings = analyzer.run_analysis(
            REPO_ROOT, package="ray_tpu", rules=("W6",),
            files=[os.path.join(REPO_ROOT, m) for m in new_modules])
        assert [f for f in findings if f.rule != "E0"] == [], \
            "new sharded modules must stay sync-free"

    def test_hunt_modules_in_w5_w6_scope_with_zero_baseline(self):
        """The r16 hunt/minimize modules are inside W5's clock-seam
        scope (the search must be a pure function of its seed — no
        wall-clock reads) AND W6's device-sync scope, and contribute
        zero baseline entries."""
        from tools.rtlint import rules_device, rules_time
        new_modules = ("ray_tpu/sim/hunt.py", "ray_tpu/sim/minimize.py")
        for mod in new_modules:
            assert os.path.exists(os.path.join(REPO_ROOT, mod))
            assert any(mod.startswith(sc) for sc in rules_time._SCOPES)
            assert mod in rules_device._EXTRA_FILES
        accepted = baseline_mod.load(os.path.join(
            REPO_ROOT, "tools", "rtlint", "baseline.json"))
        for key in accepted:
            assert not any(m in key for m in new_modules), \
                f"grandfathered finding in a new module: {key}"
        # live, not vacuous: the modules pass W5+W6 as they stand
        findings = analyzer.run_analysis(
            REPO_ROOT, package="ray_tpu", rules=("W5", "W6"),
            files=[os.path.join(REPO_ROOT, m) for m in new_modules])
        assert [f for f in findings if f.rule != "E0"] == [], \
            "hunt/minimize must stay clock- and sync-free"

    def test_budget_beat_modules_in_w5_w6_scope_with_zero_baseline(self):
        """The r17 budget-emission seam — the CPU oracle twin in
        contract.py and the beat->grantor board — is inside W6's
        device-sync scope, the board additionally inside W5's
        clock-seam scope (leasing/ prefix), and contributes zero
        grandfathered baseline entries: budgets ride the beat's one
        sanctioned readback, they never add a sync or a clock read."""
        from tools.rtlint import rules_device, rules_time
        board = "ray_tpu/leasing/board.py"
        contract = "ray_tpu/scheduling/contract.py"
        for mod in (board, contract):
            assert os.path.exists(os.path.join(REPO_ROOT, mod))
            assert any(mod.startswith(sc) for sc in rules_device._SCOPES)
        assert any(board.startswith(sc) for sc in rules_time._SCOPES)
        accepted = baseline_mod.load(os.path.join(
            REPO_ROOT, "tools", "rtlint", "baseline.json"))
        for key in accepted:
            assert board not in key and contract not in key, \
                f"grandfathered finding in a budget module: {key}"
        # live, not vacuous: both pass W5+W6 as they stand
        findings = analyzer.run_analysis(
            REPO_ROOT, package="ray_tpu", rules=("W5", "W6"),
            files=[os.path.join(REPO_ROOT, m) for m in (board, contract)])
        assert [f for f in findings if f.rule != "E0"] == [], \
            "budget seam must stay clock- and sync-free"

    def test_versioning_modules_in_scope_with_zero_baseline(self):
        """The r18 model-version plane (``ray_tpu/versioning/``) is
        inside W5's clock-seam scope (rollout timings must go through
        the seam so the sim twin replays) AND W6's device-sync scope,
        and contributes zero grandfathered baseline entries."""
        from tools.rtlint import rules_device, rules_time
        new_modules = ("ray_tpu/versioning/registry.py",
                       "ray_tpu/versioning/rollout.py",
                       "ray_tpu/versioning/phases.py")
        for mod in new_modules:
            assert os.path.exists(os.path.join(REPO_ROOT, mod))
            assert any(mod.startswith(sc) for sc in rules_time._SCOPES)
            assert any(mod.startswith(sc) for sc in rules_device._SCOPES)
        accepted = baseline_mod.load(os.path.join(
            REPO_ROOT, "tools", "rtlint", "baseline.json"))
        for key in accepted:
            assert "ray_tpu/versioning/" not in key, \
                f"grandfathered finding in a new module: {key}"
        # live, not vacuous: the package passes W5+W6 as it stands
        findings = analyzer.run_analysis(
            REPO_ROOT, package="ray_tpu", rules=("W5", "W6"),
            files=[os.path.join(REPO_ROOT, m) for m in new_modules])
        assert [f for f in findings if f.rule != "E0"] == [], \
            "versioning plane must stay clock- and sync-free"

    def test_train_modules_in_w5_w6_scope_with_zero_baseline(self):
        """The r19 elastic training plane — the live controller and its
        simulator twin — is inside W5's clock-seam scope (restart and
        drain timings must go through the seam so goodput accounting
        replays) AND W6's device-sync scope, and contributes zero
        grandfathered baseline entries."""
        from tools.rtlint import rules_device, rules_time
        new_modules = ("ray_tpu/train/elastic.py", "ray_tpu/sim/train.py")
        for mod in new_modules:
            assert os.path.exists(os.path.join(REPO_ROOT, mod))
            assert any(mod.startswith(sc) for sc in rules_time._SCOPES)
            assert mod in rules_device._EXTRA_FILES
        accepted = baseline_mod.load(os.path.join(
            REPO_ROOT, "tools", "rtlint", "baseline.json"))
        for key in accepted:
            assert not any(m in key for m in new_modules), \
                f"grandfathered finding in a new module: {key}"
        # live, not vacuous: both pass W5+W6 as they stand
        findings = analyzer.run_analysis(
            REPO_ROOT, package="ray_tpu", rules=("W5", "W6"),
            files=[os.path.join(REPO_ROOT, m) for m in new_modules])
        assert [f for f in findings if f.rule != "E0"] == [], \
            "elastic training plane must stay clock- and sync-free"


# -- W7: lockset race detection ----------------------------------------------

class TestW7:
    def test_fires_with_both_witness_paths(self, tmp_path):
        fs = lint_snippet(tmp_path, '''
            import threading

            class Svc:
                def __init__(self):
                    self._lock = threading.Lock()
                    self.count = 0

                def bump(self):
                    self.count += 1

                def read(self):
                    return self.count
            ''', rules=("W7",))
        assert len(fs) == 1, details(fs)
        f = fs[0]
        assert f.detail == "race:Svc.count"
        # both witness access paths in the message
        assert "bump" in f.message and "read" in f.message
        assert "write at" in f.message
        assert "holding no lock" in f.message

    def test_quiet_when_guarded_by_one_lock(self, tmp_path):
        fs = lint_snippet(tmp_path, '''
            import threading

            class Svc:
                def __init__(self):
                    self._lock = threading.Lock()
                    self.count = 0

                def bump(self):
                    with self._lock:
                        self.count += 1

                def read(self):
                    with self._lock:
                        return self.count
            ''', rules=("W7",))
        assert fs == []

    def test_fires_on_thread_target_vs_api(self, tmp_path):
        fs = lint_snippet(tmp_path, '''
            import threading

            class Pump:
                def __init__(self):
                    self._lock = threading.Lock()
                    self.beats = 0
                    self._t = threading.Thread(target=self._loop,
                                               daemon=True)

                def _loop(self):
                    self.beats += 1

                def stats(self):
                    with self._lock:
                        return self.beats
            ''', rules=("W7",))
        assert len(fs) == 1, details(fs)
        assert "thread target" in fs[0].message

    def test_fires_on_timer_callback_context(self, tmp_path):
        fs = lint_snippet(tmp_path, '''
            import threading

            class Beat:
                def __init__(self, clk):
                    self._lock = threading.Lock()
                    self.ticks = 0
                    clk.call_later(1.0, self._tick)

                def _tick(self):
                    self.ticks += 1

                def read(self):
                    with self._lock:
                        return self.ticks
            ''', rules=("W7",))
        assert len(fs) == 1, details(fs)
        assert "timer callback" in fs[0].message

    def test_fires_on_escaped_handler_context(self, tmp_path):
        fs = lint_snippet(tmp_path, '''
            import threading

            class Handlers:
                def __init__(self, server):
                    self._lock = threading.Lock()
                    self.hits = 0
                    server.register({"hit": self._on_hit})

                def _on_hit(self):
                    self.hits += 1

                def read(self):
                    with self._lock:
                        return self.hits
            ''', rules=("W7",))
        assert len(fs) == 1, details(fs)
        assert "registered callback" in fs[0].message

    def test_locked_helper_propagation_is_quiet(self, tmp_path):
        """One-level interprocedural: a write inside a private helper
        called with the lock held inherits the caller's lockset."""
        fs = lint_snippet(tmp_path, '''
            import threading

            class Svc:
                def __init__(self):
                    self._lock = threading.Lock()
                    self.count = 0

                def _bump_locked(self):
                    self.count += 1

                def bump(self):
                    with self._lock:
                        self._bump_locked()

                def read(self):
                    with self._lock:
                        return self.count
            ''', rules=("W7",))
        assert fs == []

    def test_nonblocking_acquire_try_finally_is_locked(self, tmp_path):
        """The tick() idiom: acquire(blocking=False) + try/finally is
        a critical section even without a with-block."""
        fs = lint_snippet(tmp_path, '''
            import threading

            class Ticker:
                def __init__(self):
                    self._lock = threading.Lock()
                    self.ticks = 0

                def tick(self):
                    if not self._lock.acquire(blocking=False):
                        return
                    try:
                        self.ticks += 1
                    finally:
                        self._lock.release()

                def read(self):
                    with self._lock:
                        return self.ticks
            ''', rules=("W7",))
        assert fs == []

    def test_condition_aliasing_same_lock_is_quiet(self, tmp_path):
        """Condition(self._lock) IS self._lock for lockset purposes."""
        fs = lint_snippet(tmp_path, '''
            import threading

            class Q:
                def __init__(self):
                    self._lock = threading.Lock()
                    self._cv = threading.Condition(self._lock)
                    self.depth = 0

                def put(self):
                    with self._cv:
                        self.depth += 1
                        self._cv.notify()

                def drain(self):
                    with self._lock:
                        self.depth = 0
            ''', rules=("W7",))
        assert fs == []

    def test_assign_once_immutable_publish_is_quiet(self, tmp_path):
        """__init__-only writes are the immutable-publish escape."""
        fs = lint_snippet(tmp_path, '''
            import threading

            class Frozen:
                def __init__(self, rows):
                    self._lock = threading.Lock()
                    self.rows = tuple(rows)

                def read(self):
                    return self.rows

                def also_read(self):
                    return len(self.rows)
            ''', rules=("W7",))
        assert fs == []

    def test_lockless_class_out_of_scope(self, tmp_path):
        """W7 only audits classes that own at least one lock (the W1
        scope rule): plain single-threaded state holders stay quiet."""
        fs = lint_snippet(tmp_path, '''
            class Bag:
                def __init__(self):
                    self.n = 0

                def bump(self):
                    self.n += 1

                def read(self):
                    return self.n
            ''', rules=("W7",))
        assert fs == []

    def test_inline_suppression(self, tmp_path):
        fs = lint_snippet(tmp_path, '''
            import threading

            class Gauge:
                def __init__(self):
                    self._lock = threading.Lock()
                    self.hits = 0

                def bump(self):
                    # deliberately racy monotonic gauge
                    self.hits += 1  # rtlint: disable=W7

                def read(self):
                    with self._lock:
                        return self.hits
            ''', rules=("W7",))
        assert fs == []


# -- W8: replay-determinism discipline ----------------------------------------

class TestW8:
    def _lint(self, tmp_path, relpath, source):
        """W8 scopes by real package paths (sim/, chaos, the routed
        entropy seams), so fixtures mirror that tree."""
        target = tmp_path / relpath
        target.parent.mkdir(parents=True, exist_ok=True)
        target.write_text(textwrap.dedent(source))
        findings = analyzer.run_analysis(
            str(tmp_path), package="ray_tpu", rules=("W8",),
            files=[str(target)])
        return [f for f in findings if f.rule != "E0"]

    def test_fires_on_global_stream_draws(self, tmp_path):
        fs = self._lint(tmp_path, "ray_tpu/sim/mod.py", '''
            import os
            import random
            import uuid
            import numpy as np
            from random import randint

            def draws():
                a = random.random()
                b = np.random.rand(3)
                c = uuid.uuid4()
                d = os.urandom(8)
                e = randint(0, 9)
                return a, b, c, d, e
            ''')
        ds = sorted(f.detail for f in fs)
        assert len(fs) == 5, ds
        assert any(d.startswith("entropy:random.random@") for d in ds)
        assert any(d.startswith("entropy:np.random.rand@") for d in ds)
        assert any(d.startswith("entropy:uuid.uuid4@") for d in ds)
        assert any(d.startswith("entropy:os.urandom@") for d in ds)
        assert any(d.startswith("entropy:random.randint@") for d in ds)

    def test_quiet_on_injected_seeded_streams(self, tmp_path):
        fs = self._lint(tmp_path, "ray_tpu/sim/mod.py", '''
            import random
            import numpy as np

            def sanctioned(seed):
                rng = random.Random(seed)
                gen = np.random.Generator(np.random.Philox(key=[seed]))
                return rng.random(), gen.random(4), rng.randint(0, 9)
            ''')
        assert fs == []

    def test_fires_on_id_and_hash(self, tmp_path):
        fs = self._lint(tmp_path, "ray_tpu/sim/mod.py", '''
            def keys(obj, name):
                return id(obj), hash(name)
            ''')
        ds = sorted(f.detail for f in fs)
        assert len(fs) == 2, ds
        assert any(d.startswith("identity:id@") for d in ds)
        assert any(d.startswith("identity:hash@") for d in ds)

    def test_fires_on_set_iteration_feeding_consumers(self, tmp_path):
        fs = self._lint(tmp_path, "ray_tpu/sim/mod.py", '''
            PENDING = {"a", "b", "c"}

            def schedule(emit):
                for name in PENDING:
                    emit(name)

            def through_list(emit):
                for name in list(PENDING):
                    emit(name)

            def comprehended():
                return [n.upper() for n in PENDING]
            ''')
        assert len(fs) == 3, sorted(f.detail for f in fs)
        assert all(d.startswith("setiter:") for _, d in details(fs))

    def test_sorted_and_setcomp_and_dict_are_quiet(self, tmp_path):
        fs = self._lint(tmp_path, "ray_tpu/sim/mod.py", '''
            PENDING = {"a", "b", "c"}
            TABLE = {"x": 1}

            def sorted_loop(emit):
                for name in sorted(PENDING):
                    emit(name)

            def sorted_genexp():
                return sorted(n.upper() for n in PENDING)

            def to_set():
                return {n.upper() for n in PENDING}

            def dict_loop(emit):
                # plain dicts are insertion-ordered: legal
                for k, v in TABLE.items():
                    emit(k, v)
            ''')
        assert fs == [], details(fs)

    def test_out_of_scope_and_suppressed_sites_quiet(self, tmp_path):
        # outside sim scope: free to draw
        fs = self._lint(tmp_path, "ray_tpu/serve/mod.py", '''
            import random

            def jitter():
                return random.random()
            ''')
        assert fs == []
        # deliberate process-local identity, visibly annotated
        fs = self._lint(tmp_path, "ray_tpu/sim/mod.py", '''
            def pace_key(sock):
                return id(sock)  # rtlint: disable=W8
            ''')
        assert fs == []


# -- W7/W8 over the live package ---------------------------------------------

class TestW7W8LivePackage:
    BASELINE = os.path.join(REPO_ROOT, "tools", "rtlint",
                            "baseline.json")

    def test_w7_green_and_satellite_files_unbaselined(self):
        """The race fixes are real fixes, not baseline entries: the
        serve/loaning/metrics counters and the other files this PR
        repaired contribute ZERO grandfathered W7 findings."""
        new, based, stale, _ = analyzer.check(
            REPO_ROOT, "ray_tpu", rules=("W7",),
            baseline_path=self.BASELINE)
        assert new == [], [f.format_text() for f in new]
        assert based, "W7 found nothing on the live package — broken?"
        fixed = ("ray_tpu/serve/loaning.py", "ray_tpu/serve/gossip.py",
                 "ray_tpu/serve/router.py",
                 "ray_tpu/scheduling/cluster_resources.py",
                 "ray_tpu/runtime/runtime_env.py",
                 "ray_tpu/runtime/job_manager.py")
        for f in based:
            assert f.path not in fixed, \
                f"grandfathered W7 in a repaired file: {f.fingerprint}"

    def test_w8_green_with_zero_baseline(self):
        """Every W8 finding was FIXED (entropy routed through seams,
        set iterations sorted) or inline-justified — none
        grandfathered."""
        new, based, stale, _ = analyzer.check(
            REPO_ROOT, "ray_tpu", rules=("W8",),
            baseline_path=self.BASELINE)
        assert new == [], [f.format_text() for f in new]
        assert based == [], [f.fingerprint for f in based]
        accepted = baseline_mod.load(self.BASELINE)
        assert not any(k.startswith("W8:") for k in accepted)


# -- runtime lockset recorder -------------------------------------------------

class TestRuntimeLocksets:
    @pytest.fixture(autouse=True)
    def _clean(self):
        from ray_tpu.common import locksets
        was = locksets.installed()
        yield
        if not was:
            locksets.uninstall()
        locksets.reset()

    def test_config_gate(self):
        from ray_tpu.common import locksets
        from ray_tpu.common.config import Config
        if locksets.installed():
            pytest.skip("suite already runs with the recorder installed")
        Config.reset()
        assert locksets.maybe_install_from_config() is False
        Config.reset(system_config={"rtlint_runtime_locksets": True})
        assert locksets.maybe_install_from_config() is True
        assert locksets.installed()

    def test_seeded_race_detected(self):
        from ray_tpu.common import locksets

        @locksets.track("x", "y")
        class Box:
            def __init__(self):
                self._lock = threading.Lock()
                self.x = 0
                self.y = 0

            def locked_bump(self):
                with self._lock:
                    self.x += 1
                    self.y += 1

            def racy_bump(self):
                self.x += 1     # the seeded race

        locksets.install()
        locksets.reset()
        b = Box()
        t1 = threading.Thread(
            target=lambda: [b.locked_bump() for _ in range(100)])
        t2 = threading.Thread(
            target=lambda: [b.racy_bump() for _ in range(100)])
        t1.start(); t2.start(); t1.join(5.0); t2.join(5.0)
        v = locksets.violations()
        assert any("Box.x" in s for s in v), v
        assert not any("Box.y" in s for s in v), v
        with pytest.raises(AssertionError, match="empty-lockset"):
            locksets.assert_no_races()

    def test_clean_class_stays_quiet(self):
        from ray_tpu.common import locksets

        @locksets.track("n")
        class Clean:
            def __init__(self):
                self._lock = threading.Lock()
                self.n = 0

            def bump(self):
                with self._lock:
                    self.n += 1

        locksets.install()
        locksets.reset()
        c = Clean()
        ts = [threading.Thread(
            target=lambda: [c.bump() for _ in range(100)])
            for _ in range(4)]
        [t.start() for t in ts]
        [t.join(5.0) for t in ts]
        assert c.n == 400
        assert locksets.violations() == []
        locksets.assert_no_races()

    def test_init_writes_are_immutable_publish(self):
        """Constructor writes never sample: assign-once publish stays
        quiet even when another thread writes later WITH the lock."""
        from ray_tpu.common import locksets

        @locksets.track("rows")
        class Pub:
            def __init__(self, rows):
                self._lock = threading.Lock()
                self.rows = tuple(rows)     # unlocked: __init__ only

            def replace(self, rows):
                with self._lock:
                    self.rows = tuple(rows)

        locksets.install()
        locksets.reset()
        p = Pub([1, 2])
        t = threading.Thread(target=lambda: p.replace([3]))
        t.start(); t.join(5.0)
        p.replace([4])
        # two threads wrote, but all SAMPLED writes held the lock
        assert locksets.violations() == []

    def test_tracked_serve_boards_register(self):
        """The live serve boards opted in: constructing them under the
        recorder samples their counters (clean single-threaded use)."""
        from ray_tpu.common import locksets
        from ray_tpu.serve.gossip import LoadBoard
        locksets.install()
        locksets.reset()
        board = LoadBoard()
        board.fold("base", {0: {b"k": 1}}, {b"k"})
        assert board.folds == 1
        assert locksets.violations() == []


# -- SARIF output -------------------------------------------------------------

class TestSarif:
    def _run(self, *extra):
        proc = subprocess.run(
            [sys.executable, "-m", "tools.rtlint", "--format=sarif",
             f"--root={REPO_ROOT}", *extra],
            capture_output=True, text=True, timeout=120)
        return proc, json.loads(proc.stdout)

    def test_green_run_emits_suppressed_baseline(self):
        proc, doc = self._run()
        assert proc.returncode == 0
        assert doc["version"] == "2.1.0"
        run = doc["runs"][0]
        assert run["tool"]["driver"]["name"] == "rtlint"
        rule_ids = {r["id"] for r in run["tool"]["driver"]["rules"]}
        assert {"W7", "W8"} <= rule_ids
        results = run["results"]
        assert results, "baselined findings must still be emitted"
        for r in results:
            assert r["suppressions"][0]["kind"] == "external"
            assert r["level"] == "note"
            assert r["partialFingerprints"]["rtlint/v1"]
            loc = r["locations"][0]["physicalLocation"]
            assert loc["artifactLocation"]["uri"].startswith("ray_tpu/")

    def test_no_baseline_run_emits_warnings(self):
        proc, doc = self._run("--no-baseline", "--rules=W7")
        assert proc.returncode == 1
        results = doc["runs"][0]["results"]
        assert results
        for r in results:
            assert r["level"] == "warning"
            assert "suppressions" not in r


# -- AST cache: single parse per file -----------------------------------------

class TestAstCache:
    def test_full_run_parses_each_file_once(self):
        analyzer.clear_cache()
        files = analyzer.iter_package_files(
            os.path.join(REPO_ROOT, "ray_tpu"))
        before = analyzer.parse_count()
        analyzer.run_analysis(REPO_ROOT, "ray_tpu")     # all 8 rules
        first = analyzer.parse_count() - before
        assert first == len(files), \
            f"{first} parses for {len(files)} files — cache broken"
        # a second full run re-parses NOTHING (content unchanged)
        analyzer.run_analysis(REPO_ROOT, "ray_tpu")
        assert analyzer.parse_count() - before == first

    def test_cache_invalidates_on_content_change(self, tmp_path):
        mod = tmp_path / "fixturepkg" / "mod.py"
        mod.parent.mkdir(parents=True)
        (mod.parent / "common").mkdir()
        (mod.parent / "common" / "config.py").write_text(CONFIG_STUB)
        mod.write_text("x = 1\n")
        analyzer.run_analysis(str(tmp_path), package="fixturepkg",
                              rules=("W4",))
        before = analyzer.parse_count()
        analyzer.run_analysis(str(tmp_path), package="fixturepkg",
                              rules=("W4",))
        assert analyzer.parse_count() == before     # warm hit
        mod.write_text("x = 2\n")
        analyzer.run_analysis(str(tmp_path), package="fixturepkg",
                              rules=("W4",))
        assert analyzer.parse_count() == before + 1  # re-parsed once

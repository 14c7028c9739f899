"""rlint: static analyzer (R001–R007), baseline round-trip, LockWitness,
and the tier-1 gate holding rl_tpu/ at zero unsuppressed findings.

Rule fixtures are in-memory sources (``analyze_sources``) so each case
states exactly the code shape it exercises: a positive that must fire
and a negative that must stay silent. The gate test at the bottom is the
CI contract from ISSUE 8: ``python tools/rlint.py rl_tpu/`` exits 0 —
now under ``--strict`` (stale suppressions fail too). The IR tier
(R101–R105) has its own fixtures in tests/test_ir_audit.py.
"""

import json
import os
import subprocess
import sys
import threading
import time

import pytest

from rl_tpu.analysis import (
    Baseline,
    LockWitness,
    analyze_paths,
    analyze_sources,
    hot_path,
)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def rules_of(findings):
    return sorted({f.rule for f in findings})


# ---------------------------------------------------------------------------
# R001: host sync in hot path
# ---------------------------------------------------------------------------


class TestR001:
    def test_item_in_scan_body_flagged(self):
        src = """
import jax
import jax.numpy as jnp

def body(carry, x):
    bad = carry.item()
    return carry + x, bad

def run(xs):
    return jax.lax.scan(body, jnp.zeros(()), xs)
"""
        out = analyze_sources({"m": src}, rules=["R001"])
        assert [f.qualname for f in out] == ["body"]
        assert ".item()" in out[0].message

    def test_hot_path_decorated_loop_flagged(self):
        src = """
import numpy as np
from rl_tpu.analysis import hot_path

@hot_path(reason="dispatch loop")
def loop(dev_arrays):
    for a in dev_arrays:
        host = np.asarray(a)
    return host
"""
        out = analyze_sources({"m": src}, rules=["R001"])
        assert [f.qualname for f in out] == ["loop"]

    def test_reachability_through_helper(self):
        src = """
import jax

def helper(x):
    return float(x)

@jax.jit
def hot(x):
    return helper(x)
"""
        out = analyze_sources({"m": src}, rules=["R001"])
        assert [f.qualname for f in out] == ["helper"]
        assert "called from hot" in out[0].message

    def test_cold_function_not_flagged(self):
        src = """
import numpy as np

def checkpoint_meta(state):
    return {"step": int(state["step"]), "loss": float(state["loss"])}
"""
        assert analyze_sources({"m": src}, rules=["R001"]) == []

    def test_float_of_literal_not_flagged(self):
        src = """
import jax

@jax.jit
def hot(x):
    return x * float(1e-4)
"""
        assert analyze_sources({"m": src}, rules=["R001"]) == []


# ---------------------------------------------------------------------------
# R002: donation-after-use
# ---------------------------------------------------------------------------


class TestR002:
    SRC = """
import jax

def _step(state, batch):
    return state

step = jax.jit(_step, donate_argnums=(0,))

def bad(state, batch):
    new = step(state, batch)
    return state  # donated buffer referenced after dispatch

def ok(state, batch):
    state = step(state, batch)
    return state
"""

    def test_use_after_donation_flagged(self):
        out = analyze_sources({"m": self.SRC}, rules=["R002"])
        assert [f.qualname for f in out] == ["bad"]

    def test_rebound_not_flagged(self):
        out = analyze_sources({"m": self.SRC}, rules=["R002"])
        assert "ok" not in [f.qualname for f in out]

    def test_loop_carried_donation_flagged(self):
        src = """
import jax

def _step(state):
    return state

step = jax.jit(_step, donate_argnums=(0,))

def train(state):
    for _ in range(10):
        out = step(state)  # state donated on iter 0, reused on iter 1
    return out
"""
        out = analyze_sources({"m": src}, rules=["R002"])
        assert [f.qualname for f in out] == ["train"]

    # the serving engine's idiom: programs made by a registry's
    # ``register(..., donate_argnums=...)``, handed out by a getter
    ENGINE = """
class Engine:
    def _get_prog(self, k):
        prog = self._progs.get(k)
        if prog is None:
            prog = self._progs[k] = self._registry.register(
                f"decode.k{k}", self._fn, donate_argnums=(1,))
        return prog

    def _get_copy(self, n):
        return self._registry.register("copy", self._copy_fn, donate_argnums=(0,))

    def ok(self, k):
        pools = self.cache
        if self.flag:
            tok, new_pools = self._get_prog(k)(self.params, pools)
        else:
            prog = self._get_prog(k)
            tok, new_pools = prog(self.params, pools)
        self.cache = new_pools
        return tok

    def reads_consumed(self, k):
        pools = self.cache
        prog = self._get_prog(k)
        tok, new_pools = prog(self.params, pools)
        self.cache = new_pools
        return pools[0].sum()

    def direct_call_reads_consumed(self, n, src, dst):
        pools = self.cache
        self.cache = self._get_copy(n)(pools, src, dst)
        return pools

    def loop_without_rebinding(self, k):
        pools = self.cache
        prog = self._get_prog(k)
        for _ in range(3):
            out = prog(self.params, pools)
        return out
"""

    def test_registry_programs_behind_getters_are_tracked(self):
        out = analyze_sources({"m": self.ENGINE}, rules=["R002"])
        assert sorted(f.qualname for f in out) == [
            "Engine.direct_call_reads_consumed",
            "Engine.loop_without_rebinding",
            "Engine.reads_consumed",
        ]

    def test_serving_engine_donations_are_seen_and_clean(self):
        """The rule sees every call of a pool-taking engine program (a
        clean result is not an empty one) and none reads a consumed pool."""
        from rl_tpu.analysis.core import ModuleIndex
        from rl_tpu.analysis.rules import donating_calls

        path = os.path.join(REPO, "rl_tpu", "models", "serving.py")
        with open(path) as f:
            m = ModuleIndex("rl_tpu.models.serving", "rl_tpu/models/serving.py", f.read())
        seen = {(fn.display.split(".")[-1], pos[0]) for fn, _, _, pos in donating_calls(m)}
        assert seen == {
            ("_admit", (1,)), ("_launch", (1,)), ("_launch_spec", (1,)),
            ("prefill_detached", (1,)), ("_dispatch_cow", (0,)),
        }
        out = analyze_paths([path], root=REPO, rules=["R002"])
        assert out == [], [f.format() for f in out]


# ---------------------------------------------------------------------------
# R003: PRNG key reuse
# ---------------------------------------------------------------------------


class TestR003:
    def test_reuse_flagged(self):
        src = """
import jax

def sample(key):
    a = jax.random.uniform(key, (3,))
    b = jax.random.normal(key, (3,))
    return a + b
"""
        out = analyze_sources({"m": src}, rules=["R003"])
        assert len(out) == 1 and out[0].qualname == "sample"

    def test_split_between_uses_ok(self):
        src = """
import jax

def sample(key):
    k1, k2 = jax.random.split(key)
    a = jax.random.uniform(k1, (3,))
    b = jax.random.normal(k2, (3,))
    return a + b
"""
        assert analyze_sources({"m": src}, rules=["R003"]) == []

    def test_exclusive_branches_ok(self):
        # the Bounded.rand shape that produced rlint's first false positive:
        # consumption on a `return`-terminated branch must not leak into the
        # fall-through path
        src = """
import jax

def rand(key, integer):
    if integer:
        return jax.random.randint(key, (3,), 0, 7)
    return jax.random.uniform(key, (3,))
"""
        assert analyze_sources({"m": src}, rules=["R003"]) == []

    def test_loop_carried_reuse_flagged(self):
        src = """
import jax

def rollout(key, n):
    total = 0.0
    for _ in range(n):
        total += jax.random.uniform(key, ())
    return total
"""
        out = analyze_sources({"m": src}, rules=["R003"])
        assert len(out) == 1 and "loop" in out[0].message


# ---------------------------------------------------------------------------
# R004: recompile hazards
# ---------------------------------------------------------------------------


class TestR004:
    def test_tracer_branch_flagged(self):
        src = """
import jax

@jax.jit
def f(x):
    if x > 0:
        return x
    return -x
"""
        out = analyze_sources({"m": src}, rules=["R004"])
        assert len(out) == 1 and out[0].qualname == "f"

    def test_static_argname_branch_ok(self):
        src = """
from functools import partial
import jax

@partial(jax.jit, static_argnames=("training",))
def f(x, training):
    if training:
        return x * 2
    return x
"""
        assert analyze_sources({"m": src}, rules=["R004"]) == []

    def test_shape_branch_ok(self):
        src = """
import jax

@jax.jit
def f(x):
    if x.ndim == 2:
        return x.sum(axis=1)
    return x
"""
        assert analyze_sources({"m": src}, rules=["R004"]) == []

    def test_jit_in_loop_flagged(self):
        src = """
import jax

def train(xs):
    out = []
    for x in xs:
        out.append(jax.jit(lambda v: v * 2)(x))
    return out
"""
        out = analyze_sources({"m": src}, rules=["R004"])
        assert len(out) == 1 and "loop" in out[0].message


# ---------------------------------------------------------------------------
# R006: ProgramRegistry bypass in models/ and trainers/
# ---------------------------------------------------------------------------


class TestR006:
    SRC = """
import jax
from functools import partial

def build(fn):
    step = jax.jit(fn, donate_argnums=(0,))
    return step

@jax.jit
def decorated(x):
    return x + 1

@partial(jax.jit, static_argnames=("n",))
def partial_decorated(x, n):
    return x * n
"""

    def test_flagged_inside_scope(self):
        out = analyze_sources({"rl_tpu.models.m": self.SRC}, rules=["R006"])
        assert len(out) == 3
        assert all("ProgramRegistry" in f.message for f in out)
        out = analyze_sources({"rl_tpu.trainers.m": self.SRC}, rules=["R006"])
        assert len(out) == 3

    def test_other_packages_not_flagged(self):
        # the rule is scoped: collectors/, ops/, tools keep raw jit freely
        assert analyze_sources({"rl_tpu.collectors.m": self.SRC},
                               rules=["R006"]) == []
        assert analyze_sources({"rl_tpu.ops.m": self.SRC}, rules=["R006"]) == []

    def test_registry_dispatch_not_flagged(self):
        src = """
from rl_tpu.compile import get_program_registry

def build(fn, cfg):
    reg = get_program_registry()
    return reg.register("m.step", fn, fingerprint=repr(cfg),
                        donate_argnums=(0,))
"""
        assert analyze_sources({"rl_tpu.models.m": src}, rules=["R006"]) == []


# ---------------------------------------------------------------------------
# R007: cross-thread shared-state hazard
# ---------------------------------------------------------------------------


class TestR007:
    SRC = """
import threading

class Worker:
    def __init__(self):
        self._lock = threading.Lock()
        self._count = 0
        self._total = 0
        self._thread = None

    def start(self):
        self._thread = threading.Thread(target=self._loop)
        self._thread.start()

    def _loop(self):
        while True:
            self._count += 1
            with self._lock:
                self._total += 1

    def stats(self):
        return {"count": self._count, "total": self._peek()}

    def _peek(self):
        with self._lock:
            return self._total
"""

    def test_unlocked_cross_thread_attr_flagged(self):
        out = analyze_sources({"m": self.SRC}, rules=["R007"])
        assert len(out) == 1
        assert "_count" in out[0].message
        assert out[0].qualname.startswith("Worker")

    def test_locked_attr_not_flagged(self):
        out = analyze_sources({"m": self.SRC}, rules=["R007"])
        assert not any("_total" in f.message for f in out)

    def test_supervisor_spawn_target_flagged(self):
        src = """
class Service:
    def __init__(self, sup):
        self._sup = sup
        self._beats = 0

    def start(self):
        self._sup.spawn("svc", self._run)

    def _run(self):
        self._beats += 1

    def health(self):
        return self._beats
"""
        out = analyze_sources({"m": src}, rules=["R007"])
        assert len(out) == 1 and "_beats" in out[0].message

    def test_both_sides_locked_clean(self):
        src = """
import threading

class Service:
    def __init__(self, sup):
        self._sup = sup
        self._lock = threading.Lock()
        self._beats = 0

    def start(self):
        self._sup.spawn("svc", self._run)

    def _run(self):
        with self._lock:
            self._beats += 1

    def health(self):
        with self._lock:
            return self._beats
"""
        assert analyze_sources({"m": src}, rules=["R007"]) == []

    def test_thread_safe_primitives_excluded(self):
        src = """
import queue
import threading

class Pump:
    def __init__(self):
        self._q = queue.Queue()
        self._stop = threading.Event()

    def start(self):
        threading.Thread(target=self._loop).start()

    def _loop(self):
        while not self._stop.is_set():
            self._q.put(1)

    def drain(self):
        return self._q.get()

    def stop(self):
        self._stop.set()
"""
        assert analyze_sources({"m": src}, rules=["R007"]) == []

    def test_no_thread_no_finding(self):
        src = """
class Plain:
    def __init__(self):
        self._n = 0

    def bump(self):
        self._n += 1

    def read(self):
        return self._n
"""
        assert analyze_sources({"m": src}, rules=["R007"]) == []


# ---------------------------------------------------------------------------
# R005: static lock order
# ---------------------------------------------------------------------------


class TestR005:
    CYCLE = """
import threading

class A:
    _lock = threading.Lock()

    def use_b(self, b):
        with self._lock:
            b.locked_b()

    def locked_a(self):
        with self._lock:
            pass

class B:
    _lock = threading.Lock()

    def locked_b(self):
        with self._lock:
            pass

    def use_a(self, a):
        with self._lock:
            a.locked_a()
"""

    def test_cross_class_cycle_flagged(self):
        out = analyze_sources({"m": self.CYCLE}, rules=["R005"])
        assert out, "expected a lock-order cycle"
        assert any("cycle" in f.message for f in out)

    def test_consistent_order_ok(self):
        src = """
import threading

class A:
    _lock = threading.Lock()

    def f(self, b):
        with self._lock:
            b.g()

class B:
    _lock = threading.Lock()

    def g(self):
        with self._lock:
            pass
"""
        assert analyze_sources({"m": src}, rules=["R005"]) == []

    def test_self_deadlock_flagged(self):
        src = """
import threading

class A:
    _lock = threading.Lock()

    def f(self):
        with self._lock:
            with self._lock:
                pass
"""
        out = analyze_sources({"m": src}, rules=["R005"])
        assert len(out) == 1 and "self-deadlock" in out[0].message

    def test_rlock_reentry_ok(self):
        src = """
import threading

class A:
    _lock = threading.RLock()

    def f(self):
        with self._lock:
            with self._lock:
                pass
"""
        assert analyze_sources({"m": src}, rules=["R005"]) == []


# ---------------------------------------------------------------------------
# Baseline round-trip
# ---------------------------------------------------------------------------


class TestBaseline:
    SRC = """
import jax

def sample(key):
    a = jax.random.uniform(key, (3,))
    b = jax.random.normal(key, (3,))
    return a + b
"""

    def test_suppress_and_roundtrip(self, tmp_path):
        findings = analyze_sources({"m": self.SRC}, rules=["R003"])
        assert len(findings) == 1
        path = str(tmp_path / "baseline.json")
        b = Baseline(path=path)
        unsup, sup, stale = b.split(findings)
        assert len(unsup) == 1 and not sup and not stale

        b.add(findings[0], "intentional: fixture")
        b.save(path)
        b2 = Baseline.load(path)
        unsup, sup, stale = b2.split(findings)
        assert not unsup and len(sup) == 1 and not stale

        # stale detection: suppression survives, finding is gone
        unsup, sup, stale = b2.split([])
        assert not unsup and not sup and len(stale) == 1

    def test_reason_required(self, tmp_path):
        path = str(tmp_path / "baseline.json")
        with open(path, "w") as f:
            json.dump({"suppressions": [{"fingerprint": "abc", "reason": ""}]}, f)
        with pytest.raises(ValueError, match="reason"):
            Baseline.load(path)

    def test_fingerprint_survives_line_shift(self):
        shifted = "\n\n\n# comment\n" + self.SRC
        f1 = analyze_sources({"m": self.SRC}, rules=["R003"])[0]
        f2 = analyze_sources({"m": shifted}, rules=["R003"])[0]
        assert f1.line != f2.line
        assert f1.fingerprint == f2.fingerprint


# ---------------------------------------------------------------------------
# LockWitness (runtime)
# ---------------------------------------------------------------------------


class TestLockWitness:
    def test_two_thread_inversion_detected(self):
        w = LockWitness()
        with w:
            a = threading.Lock()
            b = threading.Lock()

            def t1():
                with a:
                    time.sleep(0.01)
                    with b:
                        pass

            def t2():
                # start after t1 releases: we want the ORDER FLIP observed,
                # not the actual deadlock
                time.sleep(0.05)
                with b:
                    with a:
                        pass

            ts = [threading.Thread(target=t1), threading.Thread(target=t2)]
            for t in ts:
                t.start()
            for t in ts:
                t.join()

        inv = w.inversions()
        assert len(inv) == 1
        assert w.stats()["inversions"] == 1

    def test_consistent_order_clean(self):
        w = LockWitness()
        with w:
            a = threading.Lock()
            b = threading.Lock()
            for _ in range(3):
                with a:
                    with b:
                        pass
        assert w.inversions() == []
        assert w.stats()["edges"] == 1

    def test_rlock_reentry_not_inversion(self):
        w = LockWitness()
        with w:
            r = threading.RLock()
            with r:
                with r:
                    pass
        assert w.inversions() == []

    def test_condition_and_queue_survive(self):
        # Condition lifts _release_save/_acquire_restore/_is_owned from the
        # wrapped lock; a Queue handoff across threads exercises all three
        import queue

        w = LockWitness()
        with w:
            q = queue.Queue()
            got = []

            def consumer():
                got.append(q.get(timeout=5))

            t = threading.Thread(target=consumer)
            t.start()
            q.put("x")
            t.join()
        assert got == ["x"]
        assert w.inversions() == []

    def test_disarm_restores_factories(self):
        orig_lock, orig_rlock = threading.Lock, threading.RLock
        w = LockWitness()
        w.arm()
        assert threading.Lock is not orig_lock
        w.disarm()
        assert threading.Lock is orig_lock
        assert threading.RLock is orig_rlock


# ---------------------------------------------------------------------------
# hot_path decorator is a transparent no-op at runtime
# ---------------------------------------------------------------------------


def test_hot_path_decorator_noop():
    @hot_path(reason="test")
    def f(x):
        return x + 1

    @hot_path
    def g(x):
        return x * 2

    assert f(1) == 2 and g(2) == 4
    assert f.__rl_tpu_hot_path__ and g.__rl_tpu_hot_path__
    assert f.__name__ == "f"


# ---------------------------------------------------------------------------
# conftest transfer-guard mode for marked hot-path tests
# ---------------------------------------------------------------------------


@pytest.mark.hot_path_guard
def test_hot_path_guard_marker_blocks_implicit_transfers():
    # on the CPU backend d2h is zero-copy (unguarded), so the observable
    # implicit transfer here is host→device: a numpy operand silently
    # uploaded into a device computation
    import jax
    import jax.numpy as jnp
    import numpy as np

    x = jnp.arange(4)  # device computation, no transfer
    with pytest.raises(Exception, match="[Dd]isallow"):
        jnp.sin(np.arange(4.0))  # implicit h2d of the numpy operand
    # explicit transfers stay allowed: the guard targets *implicit* syncs
    assert jax.device_get(x).tolist() == [0, 1, 2, 3]
    y = jax.device_put(np.arange(4))
    assert int(jax.device_get(y)[3]) == 3


def test_unmarked_tests_keep_implicit_transfers():
    import jax.numpy as jnp
    import numpy as np

    assert jnp.sin(np.arange(3.0)).shape == (3,)
    assert np.asarray(jnp.arange(3)).tolist() == [0, 1, 2]


# ---------------------------------------------------------------------------
# The tier-1 gate: rl_tpu/ is clean under the checked-in baseline
# ---------------------------------------------------------------------------


class TestPackageGate:
    def test_zero_unsuppressed_findings(self):
        findings = analyze_paths([os.path.join(REPO, "rl_tpu")], root=REPO)
        baseline = Baseline.load(os.path.join(REPO, ".rlint-baseline.json"))
        unsup, sup, stale = baseline.split(findings)
        assert not unsup, "unsuppressed rlint findings:\n" + "\n".join(
            f.format() for f in unsup
        )
        assert not stale, "stale suppressions (finding no longer fires): " + str(
            [s.get("fingerprint") for s in stale]
        )

    def test_every_suppression_has_reason(self):
        baseline = Baseline.load(os.path.join(REPO, ".rlint-baseline.json"))
        assert baseline.suppressions, "baseline unexpectedly empty"
        for s in baseline.suppressions:
            assert s.get("reason", "").strip(), f"no reason: {s}"
            assert s["reason"] != "PENDING", f"untriaged suppression: {s}"

    def test_cli_gate_exits_zero(self):
        # --strict: stale suppressions are failures, not warnings — the
        # committed baseline must be exactly the live finding set
        proc = subprocess.run(
            [sys.executable, os.path.join(REPO, "tools", "rlint.py"),
             "rl_tpu/", "--strict"],
            cwd=REPO,
            capture_output=True,
            text=True,
            timeout=120,
        )
        assert proc.returncode == 0, proc.stdout + proc.stderr

    def test_artifact_counts_consistent(self):
        path = os.path.join(REPO, "RLINT_pr15.json")
        with open(path) as f:
            art = json.load(f)
        assert art["tool"] == "rlint"
        total = art["total"]
        assert total["unsuppressed"] == 0
        assert total["found"] == total["suppressed"]
        assert total["found"] == sum(r["found"] for r in art["by_rule"].values())
        assert total["fixed_in_prs"] == len(art["fixed"])
        # the ledger carries PR 8's two genuine fixes forward
        assert any(e["pr"] == 8 and e["rule"] == "R003" for e in art["fixed"])
        assert any(e["pr"] == 8 and e["rule"] == "R001" for e in art["fixed"])
        # the deep tier is part of the committed summary: AST + IR rules,
        # every audit-set program accounted for, zero findings
        for rid in ("R007", "R101", "R102", "R103", "R104", "R105"):
            assert rid in art["rules"] and rid in art["by_rule"]
        ir = art["ir"]
        assert all(v == "ok" for v in ir["status"].values())
        assert ir["programs_audited"] >= 5
        assert "offpolicy.k_updates" in ir["by_program"]
        for name, rec in ir["by_program"].items():
            assert rec["findings"] == 0, name
        kup = ir["by_program"]["offpolicy.k_updates"]
        assert kup["donated_declared"] > 0 and kup["donated_honored"] > 0


class TestDiffMode:
    """--diff gating logic (the IR set itself is exercised in
    tests/test_ir_audit.py; here the compile is stubbed out)."""

    def _run(self, monkeypatch, changed, argv):
        import tools.rlint as rlint

        calls = {}

        def fake_run_ir(baseline_path, *, fresh_store):
            calls["fresh_store"] = fresh_store
            from rl_tpu.analysis.ir import IRAuditor

            return IRAuditor(baseline_path=baseline_path), {"stub": "ok"}

        monkeypatch.setattr(rlint, "changed_files", lambda rev: changed)
        monkeypatch.setattr(rlint, "run_ir", fake_run_ir)
        rc = rlint.main(argv)
        return rc, calls

    def test_ir_sensitive_change_reruns_ir_with_persistent_store(
        self, monkeypatch, capsys
    ):
        rc, calls = self._run(
            monkeypatch,
            ["rl_tpu/trainers/off_policy.py", "docs/static_analysis.md"],
            ["--diff", "HEAD~1"],
        )
        assert rc == 0
        # persistent store: unchanged-fingerprint programs load + skip
        assert calls == {"fresh_store": False}
        assert "IR set" in capsys.readouterr().out

    def test_non_ir_change_skips_ir(self, monkeypatch, capsys):
        rc, calls = self._run(
            monkeypatch, ["rl_tpu/obs/metrics.py"], ["--diff", "HEAD~1"]
        )
        assert rc == 0
        assert calls == {}  # run_ir never invoked
        assert "no IR-sensitive modules touched" in capsys.readouterr().out

    def test_empty_diff_is_clean_and_fast(self, monkeypatch, capsys):
        rc, calls = self._run(monkeypatch, [], ["--diff", "HEAD"])
        assert rc == 0 and calls == {}
        out = capsys.readouterr().out
        assert "0 finding(s)" in out

    def test_explicit_ir_flag_uses_fresh_store(self, monkeypatch):
        rc, calls = self._run(
            monkeypatch, ["rl_tpu/obs/metrics.py"], ["--diff", "HEAD~1", "--ir"]
        )
        assert rc == 0
        assert calls == {"fresh_store": True}

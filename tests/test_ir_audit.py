"""The rlint deep tier: jaxpr/HLO audit of every registry-compiled program.

Positive fixtures each register one deliberately poisoned program through
an ISOLATED ``ProgramRegistry(auditor=...)`` — its findings must never
reach the process-default auditor (the conftest ``pytest_sessionfinish``
gate fails the whole run on any unsuppressed R10x there) — and assert
the exact rule fires with a stable program-keyed fingerprint. Negative
coverage comes from the ``rl_tpu.compile.auditset`` set: shrunken-but-
real serving / Anakin / async off-policy programs must audit clean.
"""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from rl_tpu.analysis.ir import (
    IRAuditor,
    IRCost,
    get_ir_auditor,
    hlo_collectives,
    honored_alias_count,
    roofline,
    summarize_jaxpr,
)
from rl_tpu.compile.registry import ProgramRegistry, set_program_registry
from rl_tpu.compile.store import ExecutableStore

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture
def iso(tmp_path):
    """An isolated (registry, auditor) pair: empty baseline, throwaway
    executable store — poisoned fixture programs stay out of the
    process-default auditor and the persistent store."""
    aud = IRAuditor(baseline_path=str(tmp_path / "absent-baseline.json"))
    reg = ProgramRegistry(store=ExecutableStore(root=str(tmp_path / "store")),
                          auditor=aud)
    return reg, aud


def rules_of(findings):
    return sorted({f.rule for f in findings})


# ---------------------------------------------------------------------------
# R101: host callback in a registered program
# ---------------------------------------------------------------------------


class TestR101:
    def test_pure_callback_flagged(self, iso):
        reg, aud = iso

        def f(x):
            y = jax.pure_callback(
                lambda a: np.asarray(a) * 2.0,
                jax.ShapeDtypeStruct((4,), jnp.float32),
                x,
            )
            return y + 1.0

        prog = reg.register("fixture.callback", f)
        prog(jnp.zeros(4, jnp.float32))
        found = [f for f in aud.findings() if f.rule == "R101"]
        assert found, "pure_callback in a registered program must fire R101"
        assert found[0].file == "program:fixture.callback"
        assert "callback" in found[0].snippet

    def test_callback_free_program_clean(self, iso):
        reg, aud = iso
        prog = reg.register("fixture.clean", lambda x: jnp.sum(x * 2.0))
        prog(jnp.zeros(4, jnp.float32))
        assert "R101" not in rules_of(aud.findings())


# ---------------------------------------------------------------------------
# R102: declared donation the executable did not honor
# ---------------------------------------------------------------------------


class TestR102:
    def test_unhonorable_donation_flagged(self, iso):
        reg, aud = iso

        # the donated (64, 64) buffer matches no output shape: XLA can't
        # alias it, the donation silently buys nothing
        def f(a, b):
            return jnp.sum(a) + jnp.sum(b)

        prog = reg.register("fixture.baddon", f, donate_argnums=(0,))
        prog(jnp.zeros((64, 64), jnp.float32), jnp.zeros(3, jnp.float32))
        found = [f for f in aud.findings() if f.rule == "R102"]
        assert found and found[0].file == "program:fixture.baddon"

    def test_honored_donation_clean(self, iso):
        reg, aud = iso
        prog = reg.register("fixture.gooddon", lambda a: a + 1.0,
                            donate_argnums=(0,))
        prog(jnp.zeros((64, 64), jnp.float32))
        assert "R102" not in rules_of(aud.findings())
        rep = aud.report_for("fixture.gooddon")
        assert rep.donated_declared >= 1
        assert rep.donated_honored >= 1

    def test_no_donation_declared_clean(self, iso):
        reg, aud = iso
        prog = reg.register("fixture.nodon", lambda a, b: jnp.sum(a) + jnp.sum(b))
        prog(jnp.zeros((64, 64), jnp.float32), jnp.zeros(3, jnp.float32))
        assert "R102" not in rules_of(aud.findings())


# ---------------------------------------------------------------------------
# R103: collective inside a shard-local-contract program
# ---------------------------------------------------------------------------


def _psum_prog():
    from jax.sharding import Mesh, PartitionSpec as P

    from jax import shard_map

    mesh = Mesh(np.asarray(jax.devices()[:1]), ("x",))

    def inner(x):
        return jax.lax.psum(x, "x")

    return shard_map(inner, mesh=mesh, in_specs=P("x"), out_specs=P())


class TestR103:
    def test_collective_under_contract_flagged(self, iso):
        reg, aud = iso
        prog = reg.register("fixture.coll", _psum_prog(),
                            ir_contract={"shard_local": True})
        prog(jnp.zeros((8,), jnp.float32))
        found = [f for f in aud.findings() if f.rule == "R103"]
        assert found and found[0].file == "program:fixture.coll"
        assert "psum" in found[0].snippet

    def test_collective_without_contract_clean(self, iso):
        reg, aud = iso
        prog = reg.register("fixture.coll_free", _psum_prog())
        prog(jnp.zeros((8,), jnp.float32))
        assert "R103" not in rules_of(aud.findings())


# ---------------------------------------------------------------------------
# R104: f64 creep in a ≤f32 program
# ---------------------------------------------------------------------------


class TestR104:
    def test_upcast_flagged(self, iso):
        reg, aud = iso
        with jax.enable_x64(True):
            prog = reg.register(
                "fixture.upcast",
                lambda x: jnp.sum(x.astype(jnp.float64)),
            )
            prog(jnp.zeros((16,), jnp.float32))
        found = [f for f in aud.findings() if f.rule == "R104"]
        assert found and found[0].file == "program:fixture.upcast"
        assert "float64" in found[0].snippet

    def test_declared_f64_inputs_clean(self, iso):
        # a program whose INPUTS are already f64 opted into wide math;
        # the rule only hunts silent promotion
        reg, aud = iso
        with jax.enable_x64(True):
            prog = reg.register("fixture.wide_in", lambda x: jnp.sum(x) * 2.0)
            prog(jnp.zeros((16,), jnp.float64))
        assert "R104" not in rules_of(aud.findings())


# ---------------------------------------------------------------------------
# R105: dead computation above the size threshold
# ---------------------------------------------------------------------------


class TestR105:
    def test_dead_matmul_flagged(self, iso):
        reg, aud = iso

        def f(x):
            dead = x @ x  # 64*64*4 B = 16 KiB result, never used
            return jnp.sum(x)

        prog = reg.register("fixture.dead", f)
        prog(jnp.zeros((64, 64), jnp.float32))
        found = [f for f in aud.findings() if f.rule == "R105"]
        assert found and found[0].file == "program:fixture.dead"
        assert found[0].snippet.startswith("dead:")

    def test_chain_reports_root_only(self, iso):
        reg, aud = iso

        def f(x):
            a = x @ x          # feeds only the dead root
            dead = a @ x       # the chain root
            return jnp.sum(x)

        prog = reg.register("fixture.deadchain", f)
        prog(jnp.zeros((64, 64), jnp.float32))
        found = [f for f in aud.findings() if f.rule == "R105"]
        assert len(found) == 1, [f.snippet for f in found]

    def test_small_dead_value_clean(self, iso):
        reg, aud = iso

        def f(x):
            dead = jnp.sum(x) * 3.0  # scalar, below threshold
            return x + 1.0

        prog = reg.register("fixture.smalldead", f)
        prog(jnp.zeros((64,), jnp.float32))
        assert "R105" not in rules_of(aud.findings())


# ---------------------------------------------------------------------------
# Baseline integration: IR findings suppress exactly like AST findings
# ---------------------------------------------------------------------------


class TestIRBaseline:
    def test_fingerprint_stable_and_suppressable(self, iso, tmp_path):
        reg, aud = iso

        def f(x):
            y = jax.pure_callback(
                lambda a: np.asarray(a),
                jax.ShapeDtypeStruct((4,), jnp.float32),
                x,
            )
            return y

        prog = reg.register("fixture.cbk", f)
        prog(jnp.zeros(4, jnp.float32))
        (finding,) = [f for f in aud.findings() if f.rule == "R101"]
        assert aud.unsuppressed(), "absent baseline: finding must gate"

        # suppress it, re-audit through a FRESH registry+auditor: the
        # program-keyed fingerprint (no line numbers) must match
        bpath = str(tmp_path / "baseline.json")
        with open(bpath, "w") as fh:
            json.dump({"suppressions": [{
                "fingerprint": finding.fingerprint,
                "rule": finding.rule,
                "file": finding.file,
                "qualname": finding.qualname,
                "reason": "fixture: callback is the point",
            }]}, fh)
        aud2 = IRAuditor(baseline_path=bpath)
        reg2 = ProgramRegistry(
            store=ExecutableStore(root=str(tmp_path / "store2")), auditor=aud2
        )
        prog2 = reg2.register("fixture.cbk", f)
        prog2(jnp.zeros(4, jnp.float32))
        assert [f.fingerprint for f in aud2.findings()] == [finding.fingerprint]
        assert aud2.unsuppressed() == []


# ---------------------------------------------------------------------------
# Cost model + roofline (no compile needed)
# ---------------------------------------------------------------------------


class TestCostModel:
    def test_dot_flops_exact(self):
        jaxpr = jax.make_jaxpr(lambda a, b: a @ b)(
            jnp.zeros((4, 8), jnp.float32), jnp.zeros((8, 16), jnp.float32)
        )
        facts = summarize_jaxpr(jaxpr)
        assert facts.cost.flops == 2.0 * 4 * 16 * 8
        assert facts.cost.by_prim.get("dot_general") == 1
        # io: (4*8 + 8*16 + 4*16) f32 leaves
        assert facts.cost.io_bytes == (4 * 8 + 8 * 16 + 4 * 16) * 4

    def test_scan_multiplies_body_flops(self):
        def step(c, _):
            return c @ c, None

        def f(x):
            out, _ = jax.lax.scan(step, x, None, length=10)
            return out

        jaxpr = jax.make_jaxpr(f)(jnp.zeros((8, 8), jnp.float32))
        facts = summarize_jaxpr(jaxpr)
        assert facts.cost.flops >= 10 * 2.0 * 8 * 8 * 8

    def test_roofline_bound_classification(self):
        compute = IRCost(flops=1e12, bytes=1e6)
        transfer = IRCost(flops=1e6, bytes=1e12)
        peak, bw = 1e12, 1e11
        r1 = roofline(compute, peak, bw)
        r2 = roofline(transfer, peak, bw)
        assert r1["bound"] == "compute" and not r1["transfer_bound"]
        assert r2["bound"] == "transfer" and r2["transfer_bound"]
        assert r2["predicted_mfu"] < 0.01 < r1["predicted_mfu"]

    def test_roofline_without_peak_is_intensity_only(self):
        r = roofline(IRCost(flops=100.0, bytes=50.0), 0.0)
        assert r["intensity"] == 2.0 and "predicted_s" not in r

    def test_honored_alias_count_nested_braces(self):
        hlo = ("HloModule m, input_output_alias={ {}: (0, {}, may-alias), "
               "{1}: (2, {}, must-alias) }, entry_computation_layout=...")
        assert honored_alias_count(hlo) == 2
        assert honored_alias_count("HloModule m") == 0
        assert honored_alias_count("") == 0

    def test_hlo_collectives_scan(self):
        text = "%ar = f32[8] all-reduce(f32[8] %p0), replica_groups={}"
        assert hlo_collectives(text) == ["all-reduce"]
        assert hlo_collectives("ENTRY %main { ROOT %x = add(...) }") == []


# ---------------------------------------------------------------------------
# Negative coverage: the real audit set compiles clean end to end
# ---------------------------------------------------------------------------


class TestAuditSet:
    def test_real_programs_audit_clean(self, tmp_path):
        from rl_tpu.compile.auditset import run_ir_audit

        aud = IRAuditor(baseline_path=os.path.join(REPO, ".rlint-baseline.json"))
        aud2, status = run_ir_audit(auditor=aud)
        assert aud2 is aud
        bad = {k: v for k, v in status.items() if v != "ok"}
        assert not bad, f"audit-set builders failed: {bad}"
        assert aud.programs_audited() >= 5
        names = {rep.name for rep in aud._snapshot()}
        assert "serving.admit_update" in names
        assert "anakin.dispatch" in names
        assert "offpolicy.k_updates" in names
        assert aud.unsuppressed() == [], [
            f.format() for f in aud.unsuppressed()
        ]
        # the async trainer's donation must actually be honored, program-
        # provably, not just declared
        rep = aud.report_for("offpolicy.k_updates")
        assert rep.donated_declared > 0
        assert rep.donated_honored > 0
        # every audited program carries a usable static cost
        for rep in aud._snapshot():
            assert rep.cost is not None and rep.cost.eqns > 0
        # every family of engine program that takes the KV pools was
        # audited, declares them donated, and has each one aliased
        from rl_tpu.compile.auditset import POOL_PROGRAM_FAMILIES, check_pool_programs

        check_pool_programs(aud, POOL_PROGRAM_FAMILIES)
        assert "R102" not in rules_of(aud.findings())

    def test_undonated_pool_program_fails_the_set(self, undonated_programs):
        """Take the donation off the engine's registrations (the parent's
        programs): the pool gate names the first program that copies."""
        from rl_tpu.compile.auditset import run_ir_audit

        undonated_programs()
        _, status = run_ir_audit(include=["serving_pools"])
        assert status["serving_pools"].startswith("build failed: RuntimeError: engine program")
        assert "donates 0 pool(s)" in status["serving_pools"]


# ---------------------------------------------------------------------------
# Registry wiring: reports land on the program and on /metrics
# ---------------------------------------------------------------------------


class TestRegistryWiring:
    def test_program_carries_report_and_static_cost(self, iso):
        reg, aud = iso
        prog = reg.register("fixture.wired", lambda a, b: a @ b)
        prog(jnp.zeros((16, 16), jnp.float32), jnp.zeros((16, 16), jnp.float32))
        assert prog.ir_report is not None
        assert prog.ir_report.name == "fixture.wired"
        assert prog.static_flops == 2.0 * 16 * 16 * 16
        assert prog.static_bytes > 0

    def test_env_opt_out_skips_audit(self, iso, monkeypatch):
        monkeypatch.setenv("RL_TPU_NO_IR_AUDIT", "1")
        reg, aud = iso
        prog = reg.register("fixture.optout", lambda x: x + 1.0)
        prog(jnp.zeros(4, jnp.float32))
        assert aud.programs_audited() == 0
        assert prog.ir_report is None

    def test_default_auditor_has_no_unsuppressed_findings(self):
        """The in-process shadow of the conftest sessionfinish gate: any
        program a test compiled through the DEFAULT registry so far must
        have audited clean against the checked-in baseline."""
        aud = get_ir_auditor(create=False)
        if aud is None:
            pytest.skip("no default-registry compile happened yet")
        assert aud.unsuppressed() == [], [
            f.format() for f in aud.unsuppressed()
        ]

"""Pallas kernel tier (ISSUE 17): registry, parity gates, cost pricing.

Every kernel in ``rl_tpu.kernels`` ships with a stock-XLA fallback and
is feature-detected per backend by ``kernels.registry``. Tier-1 runs on
CPU, so the kernels themselves are exercised through Pallas INTERPRET
mode (``RL_TPU_KERNELS_INTERPRET=1``) and held to their registered
exactness tier against the fallback:

- ``sampling`` / ``sumtree``: **bit-exact** — same tokens, same float
  bits, no tolerance.
- ``paged_attention`` / ``kv_int8``: **toleranced** — the online-softmax
  recurrence reorders the reduction (and int8 adds quantization error),
  so parity is numeric, plus a scale round-trip property bound.

The PR 16 seeded bit-exactness matrix re-runs at the bottom with the
fused sampler ACTIVE (and every other kernel forced off), proving the
speculative-decoding guarantee survives the kernel tier — not just the
fallback the delegation preserves by construction.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from rl_tpu.kernels import registry as kreg
from rl_tpu.kernels.kvcache import (
    dequantize,
    effective_blocks_ratio,
    init_scales,
    kv_block_bytes,
    quantize_block_write,
)
from rl_tpu.kernels.paged_attention import decode_mode, paged_flash_decode_int8
from rl_tpu.kernels.sampling import fused_sample
from rl_tpu.kernels.sumtree import sumtree_update

pytestmark = pytest.mark.usefixtures("lock_witness")

KEY = jax.random.key(0)

ALL_KERNELS = ("paged_attention", "sampling", "kv_int8", "sumtree")


@pytest.fixture
def kernels_off(monkeypatch):
    """Guarantee the stock-XLA fallback regardless of ambient env."""
    monkeypatch.delenv(kreg.ENV_INTERPRET, raising=False)
    monkeypatch.delenv(kreg.ENV_NO_KERNELS, raising=False)


@pytest.fixture
def kernels_interpret(monkeypatch):
    """Force interpret mode: real kernel lowering, no chip required."""
    monkeypatch.setenv(kreg.ENV_INTERPRET, "1")
    monkeypatch.delenv(kreg.ENV_NO_KERNELS, raising=False)


# ---------------------------------------------------------------------------
# registry: feature detection, fingerprint, status matrix


class TestRegistry:
    def test_all_four_kernels_registered(self):
        specs = kreg.registered_kernels()
        assert set(ALL_KERNELS) <= set(specs)
        for name in ALL_KERNELS:
            assert specs[name].targets, name
            assert specs[name].cost is not None, name

    def test_cpu_defaults_to_fallback(self, kernels_off):
        for name in ALL_KERNELS:
            assert kreg.selection(name) is None
            assert not kreg.expected_active(name)

    def test_native_on_supported_backend(self, kernels_off):
        assert kreg.selection("paged_attention", backend="tpu") == "native"
        assert kreg.selection("paged_attention", backend="cpu") is None

    def test_interpret_applies_off_chip_only(self, kernels_interpret):
        # the parity gate asked for the interpreter on a host backend;
        # on the chip the same override is an error, never a mode
        assert kreg.selection("sampling", backend="cpu") == "interpret"
        assert kreg.expected_active("sampling")
        with pytest.raises(RuntimeError, match=kreg.ENV_INTERPRET):
            kreg.selection("sampling", backend="tpu")

    def test_no_kernels_disables_all(self, kernels_interpret, monkeypatch):
        monkeypatch.setenv(kreg.ENV_NO_KERNELS, "1")
        for name in ALL_KERNELS:
            assert kreg.selection(name, backend="tpu") is None

    def test_no_kernels_comma_list_is_selective(self, kernels_interpret,
                                                monkeypatch):
        monkeypatch.setenv(kreg.ENV_NO_KERNELS, "sampling, sumtree")
        assert kreg.selection("sampling") is None
        assert kreg.selection("sumtree") is None
        assert kreg.selection("paged_attention") == "interpret"

    def test_fingerprint_tracks_selection(self, kernels_off, monkeypatch):
        base = kreg.kernels_fingerprint()
        assert "sampling=off" in base
        monkeypatch.setenv(kreg.ENV_INTERPRET, "1")
        on = kreg.kernels_fingerprint()
        assert on != base
        assert "sampling=interpret" in on

    def test_status_matrix(self, kernels_interpret):
        st = kreg.status()
        assert set(ALL_KERNELS) <= set(st)
        assert st["sampling"]["exactness"] == "bit-exact"
        assert st["sumtree"]["exactness"] == "bit-exact"
        assert st["paged_attention"]["exactness"] == "distribution-exact"
        assert st["kv_int8"]["exactness"] == "accuracy-gated"
        for row in st.values():
            assert row["mode"] == "interpret"


# ---------------------------------------------------------------------------
# cost model: price_call formulas + jaxpr pricing through analysis.ir


def _aval(shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype)


class TestCostModel:
    def test_price_call_matches_by_substring(self):
        got = kreg.price_call(
            "jit(_fused_sample_kernel)", [_aval((4, 64)), _aval((4, 64)),
                                          _aval((1, 1))],
            [_aval((4, 1), jnp.int32), _aval((4, 1))],
        )
        assert got is not None and got["kernel"] == "sampling"
        # softmax+noise+argmax ~ 8 flops per logit element
        assert got["flops"] == pytest.approx(8.0 * 4 * 64)
        assert got["bytes"] > 0

    def test_unknown_target_unpriced(self):
        assert kreg.price_call("some_other_call", [_aval((4, 4))], []) is None
        assert kreg.price_call("", [], []) is None

    def test_int8_target_not_shadowed_by_f32_kernel(self):
        # substring matching trap: '_paged_decode_kernel' must NOT match
        # '_paged_decode_int8_kernel' (distinct registrations, distinct
        # exactness tiers)
        table, lens = _aval((2, 4), jnp.int32), _aval((2,), jnp.int32)
        scales = _aval((12,), jnp.float32)
        q = _aval((8, 8, 16))
        kv = _aval((12, 8, 16), jnp.int8)
        got = kreg.price_call(
            "_paged_decode_int8_kernel",
            [table, lens, scales, scales, q, kv, kv], [_aval((8, 8, 16))],
        )
        assert got is not None and got["kernel"] == "kv_int8"
        f32 = kreg.price_call(
            "_paged_decode_kernel", [table, lens, q, kv, kv],
            [_aval((8, 8, 16))],
        )
        assert f32 is not None and f32["kernel"] == "paged_attention"
        # 4 flops per (row, attendable position, dim)
        assert f32["flops"] == pytest.approx(4.0 * 8 * (4 * 8) * 16)

    def test_formula_failure_degrades_to_io_bytes(self):
        # malformed avals (no shape on the operand the formula indexes):
        # price_call must still answer, never raise
        got = kreg.price_call("_paged_decode_kernel", [], [_aval((2, 2))])
        assert got is not None and got["kernel"] == "paged_attention"
        assert got["flops"] >= 0.0

    def test_jaxpr_pricing_sees_kernel_sites(self, kernels_interpret):
        from rl_tpu.analysis.ir import summarize_jaxpr

        S, V = 4, 64
        logits = jnp.zeros((S, V), jnp.float32)
        jaxpr = jax.make_jaxpr(
            lambda x, k: fused_sample(x, k, temperature=0.7)
        )(logits, KEY)
        facts = summarize_jaxpr(jaxpr)
        kernels = {k for _t, k, _p in facts.kernel_sites}
        assert "sampling" in kernels
        # the registered formula priced the call (generic rules would
        # charge the pallas_call ~0 flops)
        assert facts.cost.flops >= 8.0 * S * V

    def test_fallback_jaxpr_has_no_kernel_sites(self, kernels_off):
        from rl_tpu.analysis.ir import summarize_jaxpr

        jaxpr = jax.make_jaxpr(
            lambda x, k: fused_sample(x, k)
        )(jnp.zeros((4, 64), jnp.float32), KEY)
        assert not summarize_jaxpr(jaxpr).kernel_sites


# ---------------------------------------------------------------------------
# rlint R106: hot path on fallback


def _r106(contract, sites, name="serving.decode.k1"):
    from rl_tpu.analysis.ir import IRFacts
    from rl_tpu.analysis.irrules import run_ir_rules

    facts = IRFacts()
    facts.kernel_sites.extend(sites)
    out = run_ir_rules(name=name, facts=facts, contract=contract)
    return [f for f in out if f.rule == "R106"]


class TestR106:
    CONTRACT = {"kernel_hot_path": ("sampling",)}

    def test_fires_when_expected_kernel_missing(self, kernels_interpret):
        found = _r106(self.CONTRACT, [])
        assert len(found) == 1
        assert "sampling" in found[0].message

    def test_quiet_when_kernel_lowered(self, kernels_interpret):
        assert not _r106(
            self.CONTRACT, [("_fused_sample_kernel", "sampling", "/scan")]
        )

    def test_quiet_when_backend_unsupported(self, kernels_off):
        # CPU without interpret: fallback IS the expected lowering
        assert not _r106(self.CONTRACT, [])

    def test_quiet_when_opted_out(self, kernels_interpret, monkeypatch):
        monkeypatch.setenv(kreg.ENV_NO_KERNELS, "sampling")
        assert not _r106(self.CONTRACT, [])

    def test_int8_contract_not_satisfied_by_f32_kernel(self,
                                                       kernels_interpret):
        # the engine declares kv_int8 on quantized caches; the f32 decode
        # kernel lowering must not be accepted as satisfying it
        found = _r106(
            {"kernel_hot_path": ("kv_int8",)},
            [("_paged_decode_kernel", "paged_attention", "/scan")],
        )
        assert len(found) == 1


# ---------------------------------------------------------------------------
# fused sampling: bit-exact interpret-vs-fallback


class TestFusedSampling:
    S, V = 5, 37

    def _logits(self, shape=None):
        x = jax.random.normal(jax.random.fold_in(KEY, 9), shape or (self.S, self.V))
        # plant exact ties so first-index resolution is under test too
        return x.at[0, 5].set(x[0, 11])

    # (5, 37): one whole-array block; (16, 384): two grid steps of 8 rows
    @pytest.mark.parametrize("shape", [(5, 37), (16, 384)])
    @pytest.mark.parametrize("greedy", [True, False])
    @pytest.mark.parametrize("top_k", [0, 8])
    @pytest.mark.parametrize("per_row", [False, True])
    def test_interpret_bitwise_matches_fallback(self, monkeypatch, greedy,
                                                top_k, per_row, shape):
        x = self._logits(shape)
        key = jax.random.split(KEY, shape[0]) if per_row else KEY
        kw = dict(temperature=0.7, greedy=greedy, top_k=top_k)
        monkeypatch.delenv(kreg.ENV_INTERPRET, raising=False)
        monkeypatch.delenv(kreg.ENV_NO_KERNELS, raising=False)
        tok_fb, lp_fb = fused_sample(x, key, **kw)
        monkeypatch.setenv(kreg.ENV_INTERPRET, "1")
        tok_k, lp_k = fused_sample(x, key, **kw)
        assert np.array_equal(np.asarray(tok_fb), np.asarray(tok_k))
        # bit-exact: compare the raw float32 words, not a tolerance
        assert np.array_equal(
            np.asarray(lp_fb).view(np.uint32), np.asarray(lp_k).view(np.uint32)
        )

    def test_fallback_is_the_legacy_body(self, kernels_off):
        # PR 16's artifacts ride on this: top_k=0 fallback == the exact
        # op sequence sample_tokens always lowered
        x = self._logits()
        t = 0.7
        lps = jax.nn.log_softmax(x / t, axis=-1)
        want_tok = jax.random.categorical(KEY, lps).astype(jnp.int32)
        want_lp = jnp.take_along_axis(lps, want_tok[:, None], axis=-1)[:, 0]
        tok, lp = fused_sample(x, KEY, temperature=t)
        assert np.array_equal(np.asarray(tok), np.asarray(want_tok))
        assert np.array_equal(
            np.asarray(lp).view(np.uint32), np.asarray(want_lp).view(np.uint32)
        )

    def test_greedy_argmaxes_unscaled_logits(self, kernels_off):
        x = self._logits()
        tok, _ = fused_sample(x, KEY, temperature=0.01, greedy=True)
        assert np.array_equal(
            np.asarray(tok), np.asarray(jnp.argmax(x, axis=-1))
        )

    def test_top_k_full_vocab_is_identity(self, kernels_off):
        x = self._logits()
        a = fused_sample(x, KEY, temperature=0.9, top_k=0)
        b = fused_sample(x, KEY, temperature=0.9, top_k=self.V)
        c = fused_sample(x, KEY, temperature=0.9, top_k=self.V + 10)
        for got in (b, c):
            assert np.array_equal(np.asarray(a[0]), np.asarray(got[0]))
            assert np.array_equal(np.asarray(a[1]), np.asarray(got[1]))

    def test_top_k_restricts_support(self, kernels_off):
        x = self._logits()
        k = 4
        keep = np.asarray(jax.lax.top_k(x / 0.7, k)[1])
        for i in range(40):
            tok, lp = fused_sample(
                x, jax.random.fold_in(KEY, i), temperature=0.7, top_k=k
            )
            for s in range(self.S):
                assert int(tok[s]) in keep[s]
                assert np.isfinite(float(lp[s]))


# ---------------------------------------------------------------------------
# paged decode: int8 dequant-in-kernel vs dequantized reference


class TestPagedDecodeInt8:
    def test_decode_mode_selection(self, kernels_interpret, monkeypatch):
        assert decode_mode(int8=False) == "interpret"
        assert decode_mode(int8=True) == "interpret"
        monkeypatch.setenv(kreg.ENV_NO_KERNELS, "kv_int8")
        assert decode_mode(int8=True) is None
        assert decode_mode(int8=False) == "interpret"

    def test_int8_kernel_matches_dequantized_oracle(self):
        S, H, Hk, D = 3, 4, 2, 16
        N, Bk, maxb = 12, 8, 4
        k_f32 = jax.random.normal(jax.random.fold_in(KEY, 1), (N, Hk, Bk, D))
        v_f32 = jax.random.normal(jax.random.fold_in(KEY, 2), (N, Hk, Bk, D))
        sk = jnp.max(jnp.abs(k_f32), axis=(2, 3)) / 127.0
        sv = jnp.max(jnp.abs(v_f32), axis=(2, 3)) / 127.0
        qk = jnp.clip(jnp.round(k_f32 / sk[:, :, None, None]), -127, 127
                      ).astype(jnp.int8)
        qv = jnp.clip(jnp.round(v_f32 / sv[:, :, None, None]), -127, 127
                      ).astype(jnp.int8)
        table = np.full((S, maxb), -1, np.int32)
        lens = np.array([5, 16, 23], np.int32)
        for s in range(S):
            nb = -(-int(lens[s]) // Bk)
            table[s, :nb] = 1 + s * 3 + np.arange(nb)
        q = jax.random.normal(jax.random.fold_in(KEY, 3), (S, 1, H, D))
        out = paged_flash_decode_int8(
            q, qk, qv, sk, sv, jnp.asarray(table), jnp.asarray(lens),
            interpret=True,
        )
        # oracle: full softmax over the DEQUANTIZED pools — the kernel's
        # in-VMEM dequant must agree with materializing f32 up front
        dk = np.asarray(dequantize(qk, sk))
        dv = np.asarray(dequantize(qv, sv))
        group = H // Hk
        for s in range(S):
            L = int(lens[s])
            blocks = [b for b in table[s] if b >= 0]
            kf = np.concatenate([dk[b] for b in blocks], 1)[:, :L]
            vf = np.concatenate([dv[b] for b in blocks], 1)[:, :L]
            for h in range(H):
                kh, vh = kf[h // group], vf[h // group]
                sc = (np.asarray(q[s, 0, h]) @ kh.T) * (D**-0.5)
                p = np.exp(sc - sc.max())
                p /= p.sum()
                np.testing.assert_allclose(
                    np.asarray(out[s, 0, h]), p @ vh, rtol=1e-4, atol=1e-5
                )

    def test_rejects_multi_token_query(self):
        q = jnp.zeros((2, 3, 4, 16))
        pool = jnp.zeros((4, 2, 8, 16), jnp.int8)
        s = jnp.zeros((4, 2))
        with pytest.raises(ValueError, match="T=1"):
            paged_flash_decode_int8(
                q, pool, pool, s, s, jnp.zeros((2, 2), jnp.int32),
                jnp.zeros((2,), jnp.int32), interpret=True,
            )


# ---------------------------------------------------------------------------
# int8 KV: scale round-trip property + capacity gate + engine accuracy


class TestInt8KV:
    Hk, Bk, D = 2, 8, 4

    def _roundtrip_err(self, pool, scale, ref, blk):
        got = np.asarray(dequantize(pool, scale))[blk]
        return np.abs(got - ref), np.asarray(scale)[blk]

    def test_write_roundtrip_within_half_step(self, kernels_off):
        N = 6
        pool = jnp.zeros((N, self.Hk, self.Bk, self.D), jnp.int8)
        scale = init_scales(N, self.Hk)
        vals = jax.random.normal(KEY, (self.Bk, self.Hk, self.D)) * 3.0
        blk = jnp.full((self.Bk,), 2, jnp.int32)
        off = jnp.arange(self.Bk, dtype=jnp.int32)
        pool, scale = quantize_block_write(pool, scale, blk, off, vals)
        ref = np.moveaxis(np.asarray(vals), 0, 1)  # [Hk, Bk, D]
        err, s = self._roundtrip_err(pool, scale, ref, 2)
        # error ≤ scale/2 per element (+ float slack): half a quant step
        assert (err <= s[:, None, None] / 2 + 1e-6).all()

    def test_scale_grows_monotone_and_requantizes(self, kernels_off):
        N = 4
        pool = jnp.zeros((N, self.Hk, self.Bk, self.D), jnp.int8)
        scale = init_scales(N, self.Hk)
        small = jnp.ones((1, self.Hk, self.D)) * 0.5
        big = jnp.ones((1, self.Hk, self.D)) * 8.0
        blk = jnp.zeros((1,), jnp.int32) + 1
        pool, scale = quantize_block_write(
            pool, scale, blk, jnp.zeros((1,), jnp.int32), small
        )
        s0 = np.asarray(scale)[1].copy()
        pool, scale = quantize_block_write(
            pool, scale, blk, jnp.ones((1,), jnp.int32), big
        )
        s1 = np.asarray(scale)[1]
        assert (s1 >= s0 - 1e-9).all() and s1.max() > s0.max()
        # the earlier token was requantized under the grown scale: one
        # extra rounding, so a full step is the bound, not half
        got = np.asarray(dequantize(pool, scale))[1][:, 0]
        assert (np.abs(got - 0.5) <= s1[:, None] + 1e-6).all()
        # untouched blocks kept scale 0 and payload 0: bit-exact no-op
        assert np.asarray(scale)[[0, 2, 3]].sum() == 0.0
        assert np.asarray(pool)[[0, 2, 3]].sum() == 0

    def test_cow_copy_carries_scales(self, kernels_off):
        N = 5
        pool = jnp.zeros((N, self.Hk, self.Bk, self.D), jnp.int8)
        scale = init_scales(N, self.Hk)
        vals = jax.random.normal(jax.random.fold_in(KEY, 4),
                                 (self.Bk, self.Hk, self.D))
        blk = jnp.full((self.Bk,), 1, jnp.int32)
        off = jnp.arange(self.Bk, dtype=jnp.int32)
        pool, scale = quantize_block_write(pool, scale, blk, off, vals)
        # the engine's generic CoW: a.at[dst].set(a[src]) on every
        # block-major buffer — scales ride the same indexing as pools
        dst, src = 3, 1
        pool = pool.at[dst].set(pool[src])
        scale = scale.at[dst].set(scale[src])
        a = np.asarray(dequantize(pool, scale))
        assert np.array_equal(a[dst], a[src])

    def test_block_bytes_and_capacity_ratio(self):
        b = kv_block_bytes(16, self.Hk, self.D, int8=False)
        assert b == 2 * self.Hk * 16 * self.D * 4
        bi = kv_block_bytes(16, self.Hk, self.D, int8=True)
        assert bi == 2 * self.Hk * 16 * self.D + 2 * self.Hk * 4
        # the ISSUE capacity gate, at the serving bench's shapes
        assert effective_blocks_ratio(16, self.Hk, self.D) >= 1.8
        assert effective_blocks_ratio(16, 8, 128) >= 1.8

    def test_engine_accuracy_vs_f32(self, kernels_off):
        # accuracy-gated tier: an int8-cache engine must reproduce the
        # f32 engine's greedy tokens on short completions, with small
        # log-prob drift (pure XLA fallback read on CPU — deterministic)
        from rl_tpu.models import (
            ContinuousBatchingEngine,
            TransformerConfig,
            TransformerLM,
        )

        cfg = TransformerConfig(
            vocab_size=97, d_model=64, n_layers=2, n_heads=4, d_ff=128,
            max_seq_len=64, dtype=jnp.float32,
        )
        m = TransformerLM(cfg)
        params = m.init(KEY, jnp.zeros((1, 8), jnp.int32))["params"]
        prompts = [np.arange(3, 11) % 97, np.arange(40, 48) % 97]

        def serve(model):
            eng = ContinuousBatchingEngine(
                model, params, n_slots=2, block_size=8, n_blocks=17,
                prompt_buckets=(16,), greedy=True,
            )
            rids = [eng.submit(p, 8) for p in prompts]
            out = eng.run()
            return [out[r] for r in rids]

        ref = serve(m)
        got = serve(TransformerLM(dataclasses.replace(cfg, kv_int8=True)))
        # Greedy decoding is compared while the two engines share a
        # context. Random-init logits are nearly flat, so quantization
        # noise may flip a near-tie, after which the contexts differ and
        # the tokens say nothing; the flip itself must BE a near-tie in
        # the f32 model (the weights the installed jax draws from KEY put
        # a 0.005-nat one at the second token of prompt 2).
        n = 0
        deltas = []
        for r, g in zip(ref, got):
            agree = np.asarray(r.tokens) == np.asarray(g.tokens)
            k = len(agree) if agree.all() else int(np.argmin(agree))
            n += k
            deltas += [abs(a - b) for a, b in zip(r.log_probs[:k],
                                                  g.log_probs[:k])]
            if k < len(agree):
                ctx = np.concatenate([r.prompt, r.tokens[:k]])[None]
                logits = m.apply({"params": params}, jnp.asarray(ctx))
                top2 = jax.lax.top_k(jax.nn.log_softmax(logits[0, -1]), 2)[0]
                assert float(top2[0] - top2[1]) < 0.05, (k, top2)
        assert n >= 8, n
        assert float(np.mean(deltas)) < 0.1, deltas


# ---------------------------------------------------------------------------
# sum-tree kernel: bit parity + PER distribution under interpret


class TestSumtreeKernel:
    def _state(self, p=64, nb=4):
        pr = jax.random.uniform(jax.random.fold_in(KEY, 5), (p,)) + 0.1
        esum = pr.reshape(nb, -1).sum(axis=-1)
        return pr, esum

    # (64, 4): padded to one lane row; (4096, 256): lane-aligned, no pad
    @pytest.mark.parametrize("p,nb", [(64, 4), (4096, 256)])
    def test_interpret_bitwise_matches_fallback(self, monkeypatch, p, nb):
        pr, esum = self._state(p, nb)
        idx = jnp.asarray([3, 17, 17, 40, p - 1], jnp.int32)
        # the caller contract: duplicates pre-collapsed to the last
        # writer (non-last delta 0.0), so order can't diverge
        delta = jnp.asarray([0.5, 0.0, -0.25, 1.75, 0.125], jnp.float32)
        monkeypatch.delenv(kreg.ENV_INTERPRET, raising=False)
        monkeypatch.delenv(kreg.ENV_NO_KERNELS, raising=False)
        p_fb, e_fb = sumtree_update(pr, esum, idx, delta, fanout=p // nb)
        monkeypatch.setenv(kreg.ENV_INTERPRET, "1")
        p_k, e_k = sumtree_update(pr, esum, idx, delta, fanout=p // nb)
        assert np.array_equal(
            np.asarray(p_fb).view(np.uint32), np.asarray(p_k).view(np.uint32)
        )
        assert np.array_equal(
            np.asarray(e_fb).view(np.uint32), np.asarray(e_k).view(np.uint32)
        )

    @pytest.mark.parametrize(
        "leaves,updates,kernel",
        [(2**20, 256, True), (2**24, 256, False), (2**20, 150_000, False)],
        ids=["bench-shape", "tree-over-vmem", "batch-over-smem"],
    )
    def test_shape_rule_selects_the_path(self, kernels_interpret, leaves,
                                         updates, kernel):
        # both levels live in VMEM whole and the batch in SMEM whole; the
        # rule is on static shapes, so the jaxpr shows which path ran
        from rl_tpu.kernels import sumtree

        assert sumtree.fits(leaves, leaves // 16, updates) is kernel
        f32 = lambda n: jax.ShapeDtypeStruct((n,), jnp.float32)  # noqa: E731
        jaxpr = jax.make_jaxpr(
            lambda p, e, i, d: sumtree_update(p, e, i, d, fanout=16)
        )(f32(leaves), f32(leaves // 16),
          jax.ShapeDtypeStruct((updates,), jnp.int32), f32(updates))
        assert ("pallas_call" in str(jaxpr)) is kernel

    def test_fallback_math(self, kernels_off):
        pr, esum = self._state()
        idx = jnp.asarray([2, 20], jnp.int32)
        delta = jnp.asarray([1.0, -0.5], jnp.float32)
        p2, e2 = sumtree_update(pr, esum, idx, delta, fanout=16)
        assert float(p2[2]) == pytest.approx(float(pr[2]) + 1.0)
        assert float(e2[1]) == pytest.approx(float(esum[1]) - 0.5)

    def test_per_distribution_parity_under_interpret(self, kernels_interpret):
        # tests/test_replay.py::TestPER gate re-run with the fused
        # kernel active: index 3 carries ~92% of the mass
        from rl_tpu.data import ArrayDict, DeviceStorage, ReplayBuffer
        from rl_tpu.data.replay.samplers import PrioritizedSampler

        rb = ReplayBuffer(
            DeviceStorage(32), PrioritizedSampler(alpha=1.0, beta=1.0),
            batch_size=256,
        )
        state = rb.init(ArrayDict(x=jnp.asarray(0.0)))
        state = rb.extend(
            state, ArrayDict(x=jnp.arange(10, dtype=jnp.float32)), n=10
        )
        prio = jnp.full((10,), 0.1).at[3].set(10.0)
        state = rb.update_priority(state, jnp.arange(10), prio)
        batch, state = rb.sample(state, KEY)
        frac3 = float((np.asarray(batch["index"]) == 3).mean())
        assert frac3 > 0.7, frac3

    def test_sample_and_update_state_bit_parity(self, monkeypatch):
        from rl_tpu.data.replay.samplers import PrioritizedSampler

        cap, bs = 256, 64
        s = PrioritizedSampler(alpha=0.8)
        st0 = s.init(cap)
        st0 = s.on_write(st0, jnp.arange(200), None)
        pf = lambda idx, info: (idx % 7).astype(jnp.float32) + 0.5  # noqa: E731

        def cycle():
            st = st0
            for i in range(3):
                _idx, _info, st = s.sample_and_update(
                    st, jax.random.fold_in(KEY, i), bs,
                    jnp.asarray(200), cap, pf,
                )
            return (np.asarray(st["priorities"]).view(np.uint32),
                    np.asarray(st["esum"]).view(np.uint32))

        monkeypatch.delenv(kreg.ENV_INTERPRET, raising=False)
        monkeypatch.delenv(kreg.ENV_NO_KERNELS, raising=False)
        p_fb, e_fb = cycle()
        monkeypatch.setenv(kreg.ENV_INTERPRET, "1")
        p_k, e_k = cycle()
        assert np.array_equal(p_fb, p_k)
        assert np.array_equal(e_fb, e_k)


# ---------------------------------------------------------------------------
# PR 16 seeded bit-exactness matrix, fused sampler ACTIVE
#
# test_speculative.py already proves the matrix on the delegated
# FALLBACK (bit-identical by construction). Re-running it with ONLY the
# sampling kernel in interpret mode proves the kernel lowering itself
# preserves the guarantee — every other kernel is forced off so a
# failure points at the sampler, nothing else.

import test_speculative as _spec  # noqa: E402


class TestExactnessWithFusedSampler(_spec.TestExactness):
    @pytest.fixture(autouse=True)
    def _sampler_kernel_only(self, monkeypatch):
        monkeypatch.setenv(kreg.ENV_INTERPRET, "1")
        monkeypatch.setenv(
            kreg.ENV_NO_KERNELS, "paged_attention,kv_int8,sumtree"
        )
        yield

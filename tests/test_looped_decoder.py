"""The looped decoder (ISSUE 27): the block's variants and ``loop_steps`` on
``TransformerConfig``, held to the plain reference the benchmark uses
(``benchmarks/references/ouro-2.6b.py``, loaded by path), and the engine's
admission by the pool.

Small widths, seeded weights, ``loop_steps`` 3 (neither 2 nor the layer
count). What is compared is logits, not tokens: with random weights the
largest logit changes on rounding."""

import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from rl_tpu.models import ContinuousBatchingEngine, TransformerConfig, TransformerLM
from rl_tpu.obs import TraceRecorder, set_tracer

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load_reference():
    path = os.path.join(ROOT, "benchmarks", "references", "ouro-2.6b.py")
    spec = importlib.util.spec_from_file_location("ouro_reference", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


ref = _load_reference()

# the published keys at a small size: 2 layers run 3 times, 4 heads x 16
HF = dict(vocab_size=97, hidden_size=32, num_hidden_layers=2, num_attention_heads=4,
          num_key_value_heads=4, head_dim=16, intermediate_size=64, rms_norm_eps=1e-6,
          rope_theta=1e6, total_ut_steps=3, early_exit_threshold=1.0)
L, U = HF["num_hidden_layers"], HF["total_ut_steps"]


def lm(dtype=jnp.float32, **kw):
    base = dict(
        vocab_size=HF["vocab_size"], d_model=HF["hidden_size"], n_layers=L,
        n_heads=HF["num_attention_heads"], d_head=HF["head_dim"], d_ff=HF["intermediate_size"],
        max_seq_len=64, dtype=dtype, norm="rmsnorm", norm_eps=HF["rms_norm_eps"],
        norm_placement="sandwich", position="rotary", rope_theta=HF["rope_theta"], ffn="swiglu",
        tie_embeddings=False, loop_steps=U, scan_layers=True,
    )
    return TransformerLM(TransformerConfig(**{**base, **kw}))


def unstack(params):
    """The scanned stack's parameters as the unrolled stack names them."""
    out = {k: v for k, v in params.items() if k != "layers"}
    for i in range(L):
        out[f"h{i}"] = jax.tree.map(lambda a: a[i], params["layers"])
    return out


@pytest.fixture(scope="module")
def weights():
    """The reference's weights (bfloat16-rounded values) held in float32,
    the gate's bias moved off 0 so that the exit distribution is not flat."""
    w = jax.tree.map(lambda a: a.astype(jnp.float32), ref.make_weights(HF, 5))
    w["exit_gate"]["bias"] = jnp.asarray([0.3], jnp.float32)
    return w


@pytest.fixture(scope="module")
def tokens():
    return jax.random.randint(jax.random.key(0), (2, 12), 0, HF["vocab_size"])


@pytest.fixture(autouse=True)
def highest():
    with jax.default_matmul_precision("highest"):
        yield


# -- (a) the full forward against the reference ---------------------------------


@pytest.mark.parametrize("scan", [True, False], ids=["scanned", "unrolled"])
def test_forward_matches_reference_float32(weights, tokens, scan):
    params = weights if scan else unstack(weights)
    got = lm(scan_layers=scan).apply({"params": params}, tokens)
    want = ref.logits_fn(HF, weights, tokens)
    # float32 on both sides, the same order of operations up to fusion
    np.testing.assert_allclose(got, want, atol=2e-5)


def test_forward_matches_reference_bfloat16(weights, tokens):
    """bfloat16 activations and weights against the float32 reference: each
    of the 2 x 3 layer applications rounds a handful of [T, d] tensors to 8
    bits of mantissa (relative 2**-9), on logits whose spread is ~0.5 here:
    a mean gap of a few 1e-3 and a worst of a few 1e-2. Float8 operands
    (the benchmark's control) read 10x that."""
    w16 = jax.tree.map(lambda a: a.astype(jnp.bfloat16), weights)
    got = lm(jnp.bfloat16).apply({"params": w16}, tokens)
    want = ref.logits_fn(HF, weights, tokens)
    gap = np.abs(np.asarray(got, np.float32) - np.asarray(want))
    assert got.dtype == jnp.float32  # the head accumulates and hands on float32
    assert gap.mean() < 0.01 and gap.max() < 0.06, (gap.mean(), gap.max())
    control = np.abs(np.asarray(ref.logits_fn(HF, weights, tokens, quant="fp8")) - np.asarray(want))
    assert control.mean() > 3 * gap.mean()


def test_every_loops_logits_and_exit_distribution(weights, tokens):
    logits, aux = lm().apply({"params": weights}, tokens, return_loops=True)
    states = ref.loop_states(HF, weights, tokens)
    assert aux["logits"].shape == (U, *tokens.shape, HF["vocab_size"])
    for u in range(U):
        np.testing.assert_allclose(aux["logits"][u], ref.head(weights, states[u]), atol=2e-5)
    np.testing.assert_allclose(logits, aux["logits"][-1], atol=0)  # 1.0 picks the last loop
    p = ref.exit_distribution(HF, weights, states)
    np.testing.assert_allclose(aux["exit_p"], p, atol=1e-6)
    np.testing.assert_allclose(aux["exit_p"].sum(0), 1.0, atol=1e-6)
    assert (np.asarray(p) > 0.05).all()  # no loop's share is negligible: the test sees each


@pytest.mark.parametrize("threshold", [0.5, 0.8, 0.95])
def test_threshold_selection_matches_reference(weights, tokens, threshold):
    """p is ~(0.56, 0.25, 0.19) here: 0.5 leaves at loop 0, 0.8 at loop 1,
    0.95 at the last."""
    got = lm(early_exit_threshold=threshold).apply({"params": weights}, tokens)
    want = ref.logits_fn(dict(HF, early_exit_threshold=threshold), weights, tokens)
    np.testing.assert_allclose(got, want, atol=2e-5)
    states = ref.loop_states(HF, weights, tokens)
    at = ref.exit_loop(ref.exit_distribution(HF, weights, states), threshold)
    assert int(at[0, 0]) == {0.5: 0, 0.8: 1, 0.95: 2}[threshold]


def test_defaults_are_gpt2s():
    """The accepted cells' model is the default configuration: its
    parameter tree has the names and shapes it had."""
    cfg = TransformerConfig(vocab_size=97, d_model=32, n_layers=2, n_heads=4, d_ff=64, max_seq_len=16)
    p = TransformerLM(cfg).init(jax.random.key(0), jnp.zeros((1, 4), jnp.int32))["params"]
    assert sorted(p) == ["h0", "h1", "ln_f", "wpe", "wte"]
    assert sorted(p["h0"]) == ["attn", "down", "ln1", "ln2", "up"]
    assert sorted(p["h0"]["ln1"]) == ["bias", "scale"] and sorted(p["h0"]["up"]) == ["bias", "kernel"]
    assert cfg.head_dim == 8 and cfg.cache_entries == 2


@pytest.mark.parametrize("field,value", [("norm", "batchnorm"), ("position", "alibi"), ("ffn", "relu"),
                                         ("norm_placement", "post"), ("loop_steps", 0)])
def test_config_refuses_unknown_variants(field, value):
    with pytest.raises(ValueError, match=field):
        TransformerConfig(**{field: value})


# -- (b) prefill, then chunked decode through the caches ------------------------


def _paged_cache(model, tables, n_blocks, block=4):
    cache = model.init_paged_cache(len(tables), n_blocks, block, len(tables[0]))
    for c in cache:
        c["block_table"] = jnp.asarray(tables, jnp.int32)
        c["active"] = jnp.ones(len(tables), bool)
    return cache


def _through_cache(model, params, cache, tokens, prefill, chunk):
    """Prefill ``prefill`` tokens, then decode the rest ``chunk`` at a time."""
    step = jax.jit(lambda p, t, c: model.apply({"params": p}, t, cache=c))
    logits, cache = step(params, tokens[:, :prefill], cache)
    out = [logits]
    for t in range(prefill, tokens.shape[1], chunk):
        logits, cache = step(params, tokens[:, t:t + chunk], cache)
        out.append(logits)
    return jnp.concatenate(out, axis=1), cache


PAGED_PATHS = {
    "gather": dict(),
    "kernel": dict(flash_decode=True, flash_interpret=True),  # the Pallas kernel, interpreted
}


@pytest.mark.parametrize("scan", [True, False], ids=["scanned", "unrolled"])
@pytest.mark.parametrize("path", list(PAGED_PATHS))
def test_paged_cache_equals_full_forward(weights, tokens, scan, path):
    model = lm(scan_layers=scan, **PAGED_PATHS[path])
    params = weights if scan else unstack(weights)
    cache = _paged_cache(model, [[1, 2, 3, 4], [5, 6, 7, 8]], n_blocks=9)
    assert len(cache) == (1 if scan else U * L)
    assert sum(c["pool_k"].shape[0] for c in cache) == U * L * 9  # loops x layers entries
    got, cache = _through_cache(model, params, cache, tokens, prefill=5, chunk=1)
    np.testing.assert_allclose(got, ref.logits_fn(HF, weights, tokens), atol=3e-5)
    np.testing.assert_array_equal(cache[0]["len"], [12, 12])


@pytest.mark.parametrize("scan", [True, False], ids=["scanned", "unrolled"])
def test_paged_multi_token_decode_chunks(weights, tokens, scan):
    """Chunks of 3 tokens after the prefill (the speculative verify's shape)."""
    model = lm(scan_layers=scan)
    cache = _paged_cache(model, [[1, 2, 3, 4], [5, 6, 7, 8]], n_blocks=9)
    got, _ = _through_cache(model, weights if scan else unstack(weights), cache, tokens, prefill=6, chunk=3)
    np.testing.assert_allclose(got, ref.logits_fn(HF, weights, tokens), atol=3e-5)


@pytest.mark.parametrize("scan", [True, False], ids=["scanned", "unrolled"])
def test_contiguous_cache_equals_full_forward(weights, tokens, scan):
    model = lm(scan_layers=scan)
    cache = model.init_cache(2, 16)
    assert len(cache) == (1 if scan else U * L)
    got, cache = _through_cache(model, weights if scan else unstack(weights), cache, tokens, prefill=5, chunk=1)
    np.testing.assert_allclose(got, ref.logits_fn(HF, weights, tokens), atol=3e-5)
    assert int(cache[0]["len"]) == 12


@pytest.mark.parametrize("path", list(PAGED_PATHS))
def test_slots_of_unequal_length_rotate_by_their_own_positions(weights, path):
    """Two slots at different lengths decode in one batch: each token is
    rotated by its slot's own position (``len[s] + t``), so each row equals
    the full forward of its own sequence."""
    model = lm(**PAGED_PATHS[path])
    seqs = [jax.random.randint(jax.random.key(s), (n,), 0, HF["vocab_size"]) for s, n in ((1, 11), (2, 6))]
    cache = _paged_cache(model, [[1, 2, 3, 4], [5, 6, 7, 8]], n_blocks=9)
    # prefill 7 and 2 tokens: one padded batch, the pad masked out of the cache
    pre = (7, 2)
    tok = np.zeros((2, 7), np.int32)
    act = np.zeros((2, 7), bool)
    for s, n in enumerate(pre):
        tok[s, :n], act[s, :n] = seqs[s][:n], True
    cache[0]["active"] = jnp.asarray(act)
    logits, cache = model.apply({"params": weights}, jnp.asarray(tok), cache=cache)
    np.testing.assert_array_equal(cache[0]["len"], pre)
    rows = [[logits[s, :n]] for s, n in enumerate(pre)]
    cache[0]["active"] = jnp.ones(2, bool)
    decode = jax.jit(lambda t, c: model.apply({"params": weights}, t, cache=c))
    for t in range(4):  # both slots decode 4 more tokens, side by side
        step = jnp.stack([seqs[s][pre[s] + t] for s in range(2)])[:, None]
        logits, cache = decode(step, cache)
        for s in range(2):
            rows[s].append(logits[s])
    for s in range(2):
        want = ref.logits_fn(HF, weights, seqs[s][None, :pre[s] + 4])[0]
        np.testing.assert_allclose(jnp.concatenate(rows[s]), want, atol=3e-5)


def test_cache_paths_refuse_a_threshold_below_one(weights, tokens):
    model = lm(early_exit_threshold=0.5)
    with pytest.raises(ValueError, match="early_exit_threshold"):
        model.apply({"params": weights}, tokens, cache=model.init_cache(2, 16))
    with pytest.raises(ValueError, match="early_exit_threshold"):
        ContinuousBatchingEngine(model, weights, n_slots=2, block_size=4, n_blocks=9, prompt_buckets=(8,))


# -- (c) faults the same comparison must read as wrong --------------------------


def _lp_gap(got_logits, want_logits, tokens):
    """Mean |log-prob gap| of the next tokens: what the benchmark's ``correct`` compares."""
    pick = lambda lg: jnp.take_along_axis(  # noqa: E731
        jax.nn.log_softmax(lg[:, :-1], axis=-1), tokens[:, 1:, None], axis=-1)[..., 0]
    return float(jnp.abs(pick(got_logits) - pick(want_logits)).mean())


def test_wrong_cache_entry_and_missing_loop_read_as_wrong(weights, tokens):
    want = ref.logits_fn(HF, weights, tokens)
    params = unstack(weights)

    def served(model, rotate=0):
        """Prefill 5 tokens, decode 7; with ``rotate`` every loop decodes
        against the entries the previous loop prefilled."""
        cache = _paged_cache(model, [[1, 2, 3, 4], [5, 6, 7, 8]], n_blocks=9)
        logits, cache = model.apply({"params": params}, tokens[:, :5], cache=cache)
        out = [logits]
        cache = [cache[(e - rotate) % len(cache)] for e in range(len(cache))]
        decode = jax.jit(lambda t, c: model.apply({"params": params}, t, cache=c))
        for t in range(5, tokens.shape[1]):
            logits, cache = decode(tokens[:, t:t + 1], cache)
            out.append(logits)
        return jnp.concatenate(out, axis=1)

    sound = max(_lp_gap(served(lm(scan_layers=False)), want, tokens), 1e-6)
    assert sound < 1e-5
    # a loop that reads the previous loop's cache entry
    assert _lp_gap(served(lm(scan_layers=False), rotate=L), want, tokens) > 1000 * sound
    # a model run with one loop fewer
    assert _lp_gap(served(lm(scan_layers=False, loop_steps=U - 1)), want, tokens) > 1000 * sound
    # and the reference's own fault hook says the same of itself
    mask = jnp.ones(tokens.shape, bool)
    lp = ref.score_rows(HF, weights, tokens, mask)
    lp_fault = ref.score_rows(HF, weights, tokens, mask, fault="loops_minus_one")
    assert float(jnp.abs(lp - lp_fault)[:, 1:].mean()) > 1000 * sound


# -- (d) the training forward: loss and gradients --------------------------------


def _loss(logits, tokens):
    lp = jax.nn.log_softmax(logits[:, :-1], axis=-1)
    return -jnp.take_along_axis(lp, tokens[:, 1:, None], axis=-1).mean()


@pytest.mark.parametrize("scan", [True, False], ids=["scanned", "unrolled"])
def test_training_forward_loss_and_gradients(weights, tokens, scan):
    """Flash attention (interpreted) with remat: the loss and every leaf's
    gradient against the reference's; a shared layer's gradient is the sum
    over the loops that used it."""
    model = lm(scan_layers=scan, attention_impl="flash", flash_interpret=True, remat=True)
    params = weights if scan else unstack(weights)
    loss, grads = jax.value_and_grad(lambda p: _loss(model.apply({"params": p}, tokens), tokens))(params)
    want_loss, want = jax.value_and_grad(lambda p: _loss(ref.logits_fn(HF, p, tokens), tokens))(weights)
    np.testing.assert_allclose(loss, want_loss, rtol=1e-5)
    shared = want["layers"]["up"]["kernel"]
    if not scan:
        want = unstack(want)
    want.pop("exit_gate")  # the gate takes no part in the loss at threshold 1.0
    grads = {k: v for k, v in grads.items() if k != "exit_gate"}
    flat, _ = jax.tree_util.tree_flatten_with_path(want)
    got = dict(jax.tree_util.tree_flatten_with_path(grads)[0])
    assert len(flat) == len(got)
    for path, g in flat:
        assert float(jnp.abs(g).max()) > 0, path
        np.testing.assert_allclose(got[path], g, atol=2e-5 * float(jnp.abs(g).max()) + 1e-8, err_msg=str(path))
    # one loop alone gives another gradient: the sum over loops is what was compared
    one = jax.grad(lambda p: _loss(ref.logits_fn(HF, p, tokens, loops=1), tokens))(weights)
    assert not np.allclose(one["layers"]["up"]["kernel"], shared, rtol=0.1)


# -- (e) admission by the pool --------------------------------------------------


def _engine(weights, **kw):
    kw = {"n_slots": 4, "block_size": 4, "n_blocks": 17, "prompt_buckets": (8, 16),
          "greedy": True, "decode_chunk": 4, "seed": 3, **kw}
    return ContinuousBatchingEngine(lm(), weights, **kw)


def _no_stall(eng):
    """Fail the test if a running slot is ever refused a block."""
    ensure = eng._ensure_blocks

    def checked(slot, new_len):
        ok = ensure(slot, new_len)
        assert ok, f"slot {slot} waits for a block ({len(eng.free_blocks)} free)"
        return ok

    eng._ensure_blocks = checked


@pytest.mark.parametrize("prefix_cache", [False, True], ids=["plain", "prefix_cache"])
def test_tight_pool_finishes_every_request_at_its_budget(weights, prefix_cache):
    """16 usable blocks for 4 slots whose requests need 8 each (the cell's
    ratio: half of slots x need): prompt-only admission takes all four and
    every one of them stalls ('block pool exhausted'); admission by the pool
    runs two at a time and none ever waits."""
    eng = _engine(weights, prefix_cache=prefix_cache)
    _no_stall(eng)
    rng = np.random.default_rng(0)
    want = {}
    for i in range(10):
        prompt, new = rng.integers(0, 97, 3 + i % 2), 26 + i % 4  # 29..32 tokens: 8 blocks of 4
        want[eng.submit(prompt, new)] = (prompt, new)
    out = eng.run()
    snap = eng.metrics_snapshot()
    assert snap["admissions_deferred_kv"] > 0  # the pool, not the slots, set the batch
    assert snap["kv_reserved_blocks"] == 0 and eng.kv_free_blocks() == 16  # all of it came back
    model = lm()
    for rid, (prompt, new) in want.items():
        assert len(out[rid].tokens) == new and out[rid].finished_reason == "length"
        full = jnp.asarray(np.concatenate([prompt, out[rid].tokens]))[None]
        logits = model.apply({"params": weights}, full)[0, len(prompt) - 1:-1]
        np.testing.assert_array_equal(np.argmax(logits, -1), out[rid].tokens)


def test_reservation_is_dropped_when_a_slot_stops_early(weights):
    eng = _engine(weights, eos_id=int(1e9))  # an id no token has: nothing stops early by itself
    rid = eng.submit(np.arange(3), 29)
    eng.step()
    (slot,) = np.nonzero(eng.slot_rid == rid)[0]
    assert eng._kv_reserved() == 8 - int((eng.table[slot] >= 0).sum()) > 0
    while eng._inflight:
        eng._drain_one()
    eng._free_slot(int(slot), "eos")
    assert eng._kv_reserved() == 0 and eng._kv_available() == 16


def test_submit_refuses_what_can_never_fit(weights):
    eng = _engine(weights, n_blocks=9, max_seq_len=64)  # 8 usable blocks = 32 tokens
    eng.submit(np.arange(4), 28)
    with pytest.raises(ValueError, match="could never be admitted"):
        eng.submit(np.arange(4), 29)


def test_handoff_carries_every_cache_entry(weights):
    """``prefill_detached`` / ``adopt_handoff`` move loops x layers entries'
    blocks, whatever the pool's layout."""
    outs = []
    for handoff in (False, True):
        a = _engine(weights, kv_handoff=True)
        prompt = np.arange(5) + 7
        if handoff:
            b = _engine(weights, kv_handoff=True)
            ho = a.prefill_detached(prompt, 9)
            assert len(ho.kv) == 1 and ho.kv[0][0].shape[0] == U * L * 2  # 2 blocks x 6 entries
            rid = b.adopt_handoff(ho)
            outs.append(b.run()[rid].tokens)
        else:
            rid = a.submit(prompt, 9)
            outs.append(a.run()[rid].tokens)
    np.testing.assert_array_equal(outs[0], outs[1])


# -- (f) spans and counters -----------------------------------------------------


def test_new_span_args_and_counters(weights):
    rec = TraceRecorder()
    prev = set_tracer(rec)
    try:
        eng = _engine(weights)
        for i in range(6):
            eng.submit(np.arange(3 + i % 2), 27)
        out = eng.run()
    finally:
        set_tracer(prev)
    snap = eng.metrics_snapshot()
    decoded = snap["tokens_generated"]
    assert decoded == sum(len(f.tokens) - 1 for f in out.values())  # a prefill samples the first
    assert snap["loop_steps_run"] == U * decoded
    assert snap["cache_entries"] == U * L
    assert snap["kv_bytes_per_token"] == U * L * 2 * 4 * 16 * 4  # entries x K,V x heads x width x float32
    assert snap["admissions_deferred_kv"] > 0
    admits = [e for e in rec.export()["traceEvents"] if e["ph"] == "X" and e["name"] == "engine.admit"]
    assert admits and all(
        {"kv_free_blocks", "kv_reserved_blocks", "kv_deferred"} <= set(e["args"]) for e in admits)
    assert any(e["args"]["kv_deferred"] > 0 for e in admits)
    # a slot holds 1..8 blocks while it runs, two slots run at a time
    assert 0 < snap["kv_block_steps"] <= snap["decode_steps"] * 16
    for e in admits:
        assert e["args"]["kv_free_blocks"] >= e["args"]["kv_reserved_blocks"] >= 0


def test_sharding_rules_of_the_scanned_stack(weights):
    """A stacked leaf shards as its layers would, behind a replicated layer axis."""
    from jax.sharding import PartitionSpec as P

    from rl_tpu.models import param_sharding_rules

    rules = param_sharding_rules(weights)
    flat = param_sharding_rules(unstack(weights))
    assert rules["layers"]["attn"]["qkv"]["kernel"] == P(None, None, "model")
    assert rules["layers"]["down"]["kernel"] == P(None, "model", None)
    assert rules["layers"]["gate"]["kernel"] == P(None, None, "model") == P(None, *flat["h0"]["gate"]["kernel"])
    assert rules["layers"]["ln1"]["scale"] == P(None)
    assert rules["head"] == P(None, "model") and rules["exit_gate"]["kernel"] == P()

"""Continuous-batching engine: ragged prompts, mid-stream admission,
EOS/truncation handling, and expert telemetry vs. capture ground truth."""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.core.table import KVTable
from repro.serving import ServingEngine

from conftest import tiny_model


@pytest.fixture(scope="module")
def gpt2_moe():
    cfg, model = tiny_model("gpt2-moe")
    params = model.init_params(jax.random.PRNGKey(0))
    return cfg, model, params


def _prompts(cfg, lengths, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, cfg.vocab_size, size=n).astype(np.int32)
            for n in lengths]


# ----------------------------------------------------------- ragged prompts
def test_ragged_prompts_match_solo_decoding(gpt2_moe):
    """Slot-batched decode of ragged prompts must equal each request decoded
    alone — per-slot positions/masks leak nothing across slots or pads."""
    cfg, model, params = gpt2_moe
    prompts = _prompts(cfg, [3, 7, 5])
    eng = ServingEngine(model, params, max_len=32, batch_size=3,
                        collect_telemetry=False)
    reqs = [eng.submit(p, max_new_tokens=6) for p in prompts]
    eng.run()
    for p, r in zip(prompts, reqs):
        solo = ServingEngine(model, params, max_len=32, batch_size=1,
                             collect_telemetry=False)
        sr = solo.submit(p, max_new_tokens=6)
        solo.run()
        assert r.output == sr.output, (r.output, sr.output)
        assert r.finish_reason == "length"


def test_moe_models_prefill_exact_length(gpt2_moe):
    """Bucketed right-padding is unsafe for MoE stacks: pad tokens compete
    in the capacity-limited expert dispatch and can evict real tokens, so
    the engine must force exact-length prefill."""
    cfg, model, params = gpt2_moe
    eng = ServingEngine(model, params, max_len=32, batch_size=1,
                        collect_telemetry=False, prompt_bucket=8)
    assert eng.prompt_bucket == 1


def test_bucketed_prefill_matches_exact_for_dense():
    """For a causal full-attention dense stack, bucket padding must be
    output-invariant (pads are invisible to causal attention + masked out
    of the decode cache)."""
    cfg, model = tiny_model("codeqwen1.5-7b")
    params = model.init_params(jax.random.PRNGKey(0))
    prompts = _prompts(cfg, [3, 9, 14], seed=3)
    outs = []
    for bucket in (1, 8):
        eng = ServingEngine(model, params, max_len=32, batch_size=3,
                            collect_telemetry=False, prompt_bucket=bucket)
        assert eng.prompt_bucket == bucket
        reqs = [eng.submit(p, max_new_tokens=4) for p in prompts]
        eng.run()
        outs.append([r.output for r in reqs])
    assert outs[0] == outs[1]


def test_generated_tokens_stay_in_valid_vocab(gpt2_moe):
    """The head spans padded_vocab; sampling must be restricted to the
    valid vocab so outputs (and telemetry keys) stay in range."""
    cfg, model, params = gpt2_moe
    eng = ServingEngine(model, params, max_len=32, batch_size=2)
    for p in _prompts(cfg, [4, 6], seed=4):
        eng.submit(p, max_new_tokens=6)
    done = eng.run()
    for r in done:
        assert all(0 <= t < cfg.vocab_size for t in r.output)


# ------------------------------------------------------ mid-stream admission
def test_mid_stream_admission(gpt2_moe):
    """A request submitted AFTER run() starts lands in a freed slot and
    completes within the same run() call."""
    cfg, model, params = gpt2_moe
    pa, pb, pc = _prompts(cfg, [4, 4, 6])
    eng = ServingEngine(model, params, max_len=32, batch_size=2,
                        collect_telemetry=False)
    a = eng.submit(pa, max_new_tokens=3)    # finishes early, frees its slot
    b = eng.submit(pb, max_new_tokens=12)
    late = {}

    def on_step(engine, step):
        if step == 1:
            late["req"] = engine.submit(pc, max_new_tokens=4)

    done = eng.run(on_step=on_step)
    c = late["req"]
    assert a.done and b.done and c.done
    assert c in done
    assert c.finish_reason == "length" and len(c.output) == 4
    # admitted mid-stream: after the run started, before the long request
    # finished (i.e. while decoding was in flight), into slot freed by `a`.
    assert c.admitted_step is not None and c.admitted_step >= 1
    assert c.slot == a.slot
    assert b.finish_time > c.first_token_time


# -------------------------------------------------------------- EOS handling
def test_eos_termination(gpt2_moe):
    cfg, model, params = gpt2_moe
    (prompt,) = _prompts(cfg, [5])
    ref = ServingEngine(model, params, max_len=32, batch_size=1,
                        collect_telemetry=False)
    r0 = ref.submit(prompt, max_new_tokens=6)
    ref.run()
    assert len(r0.output) == 6
    eos = r0.output[1]        # make the 2nd generated token the stop token

    eng = ServingEngine(model, params, max_len=32, batch_size=1,
                        collect_telemetry=False)
    r = eng.submit(prompt, max_new_tokens=6, eos_id=int(eos))
    eng.run()
    assert r.finish_reason == "eos"
    assert r.output[-1] == eos
    assert len(r.output) <= 2


def test_engine_level_eos_default(gpt2_moe):
    cfg, model, params = gpt2_moe
    (prompt,) = _prompts(cfg, [5])
    ref = ServingEngine(model, params, max_len=32, batch_size=1,
                        collect_telemetry=False)
    r0 = ref.submit(prompt, max_new_tokens=6)
    ref.run()
    eng = ServingEngine(model, params, max_len=32, batch_size=1,
                        eos_id=int(r0.output[0]), collect_telemetry=False)
    r = eng.submit(prompt, max_new_tokens=6)
    eng.run()
    assert r.finish_reason == "eos" and len(r.output) == 1


# --------------------------------------------------------------- truncation
def test_truncation_is_explicit(gpt2_moe):
    """Step-budget and KV-capacity exhaustion are marked, not silent."""
    cfg, model, params = gpt2_moe
    (prompt,) = _prompts(cfg, [4])
    eng = ServingEngine(model, params, max_len=32, batch_size=1,
                        collect_telemetry=False)
    r = eng.submit(prompt, max_new_tokens=20)
    done = eng.run(max_steps=3)
    assert r in done and r.done
    assert r.finish_reason == "truncated"
    assert len(r.output) < 20

    eng2 = ServingEngine(model, params, max_len=8, batch_size=1,
                         collect_telemetry=False)
    r2 = eng2.submit(prompt, max_new_tokens=50)
    eng2.run()
    assert r2.finish_reason == "truncated"
    assert len(r2.output) < 50


def test_budget_exhaustion_keeps_unadmitted_requests_queued(gpt2_moe):
    """Only slot-resident requests are truncated by the step budget;
    never-admitted ones stay queued and are served by the next run()."""
    cfg, model, params = gpt2_moe
    p1, p2 = _prompts(cfg, [4, 5])
    eng = ServingEngine(model, params, max_len=32, batch_size=1,
                        collect_telemetry=False)
    a = eng.submit(p1, max_new_tokens=20)
    b = eng.submit(p2, max_new_tokens=3)
    done = eng.run(max_steps=2)
    assert done == [a] and a.finish_reason == "truncated"
    assert eng.pending == 1 and not b.done
    done2 = eng.run()
    assert done2 == [b] and b.finish_reason == "length"
    assert len(b.output) == 3 and eng.pending == 0


# ---------------------------------------------------------------- telemetry
def test_telemetry_matches_capture_ground_truth():
    """Engine telemetry on a served token stream == real_demand's
    capture=True ground truth, and it survives KVTable ingestion.

    The engine runs the same MoE executor as the offline profiling
    forward here ("dense"): with stacked MoE layers a later layer routes
    the PREVIOUS layer's output, so executors that differ in what they
    compute (capacity drops vs dropless) legitimately diverge in deep
    routing counts — cross-executor agreement is pinned separately in
    test_grouped_engine_telemetry_matches_grouped_capture."""
    from repro.core.runtime import RuntimeConfig, ServerlessMoERuntime

    rc = RuntimeConfig(arch="gpt2-moe", d_model_reduced=64,
                       vocab_reduced=512, seq_len=12, batch_size=4,
                       profile_batches=1, learn_batches=1, eval_batches=1)
    rt = ServerlessMoERuntime(rc)
    batch = next(rt.corpus.batches(1))["tokens"]          # (4, 12)
    real = np.sum([rt.real_demand(row[None]) for row in batch], axis=0)

    eng = ServingEngine(rt.model, rt.params, max_len=32, batch_size=2,
                        moe_executor="dense")
    for row in batch:
        eng.submit(row, max_new_tokens=0)   # prefill-only: same token stream
    done = eng.run()
    assert len(done) == len(batch)
    tel = eng.telemetry
    assert tel is not None
    np.testing.assert_array_equal(tel.demand_matrix(), real)

    # ingestion: per-key counts in the KVTable reproduce the demand matrix
    table = KVTable(rt.num_layers, rt.num_experts, rt.cfg.vocab_size)
    n = table.ingest_telemetry(tel)
    assert n > 0
    np.testing.assert_array_equal(table.demand_matrix(), real)
    # flush drains the record buffer but keeps cumulative demand
    assert table.ingest_telemetry(tel) == 0
    np.testing.assert_array_equal(tel.demand_matrix(), real)


def test_grouped_engine_telemetry_matches_grouped_capture():
    """The DEFAULT (dropless grouped) engine's demand matrix equals a
    capture=True forward through the same grouped executor, and its drop
    ledger is identically zero — the dropless guarantee, observed from
    serving telemetry."""
    from repro.core.features import extract_features
    from repro.core.runtime import RuntimeConfig, ServerlessMoERuntime

    rc = RuntimeConfig(arch="gpt2-moe", d_model_reduced=64,
                       vocab_reduced=512, seq_len=12, batch_size=4,
                       profile_batches=1, learn_batches=1, eval_batches=1)
    rt = ServerlessMoERuntime(rc)
    batch = next(rt.corpus.batches(1))["tokens"]

    real = np.zeros((rt.num_layers, rt.num_experts))
    for row in batch:
        _, aux, _ = rt.model.forward(rt.params, jnp.asarray(row[None]),
                                     capture=True, moe_executor="grouped")
        caps = jax.tree.map(np.asarray, aux["captures"])
        for r in extract_features(row[None], caps, len(rt.cfg.pattern)):
            np.add.at(real[r.layer], r.experts.ravel(), 1.0)

    eng = ServingEngine(rt.model, rt.params, max_len=32, batch_size=2)
    assert eng.moe_executor == "grouped"    # serving default is dropless
    for row in batch:
        eng.submit(row, max_new_tokens=0)
    eng.run()
    np.testing.assert_array_equal(eng.telemetry.demand_matrix(), real)
    assert eng.telemetry.dropped_matrix().sum() == 0.0


def test_dense_engine_reports_capacity_drops():
    """Forcing the dense executor on a batch that overflows capacity
    surfaces a nonzero drop ledger — the tax the grouped default
    removes. (Drops are counted per decoded batch, padding slots
    included: the summary is batch-level, exactly what the dense path
    computed.)"""
    # cf=0.5 with 56-token prompts over 4 experts: capacity rounds to 8
    # but SOME expert must receive >= ceil(56/4) = 14 pairs (pigeonhole),
    # so the dense prefill provably drops
    cfg, model = tiny_model("gpt2-moe", capacity_factor=0.5)
    params = model.init_params(jax.random.PRNGKey(0))
    prompts = _prompts(cfg, [56, 50, 52])
    dense = ServingEngine(model, params, max_len=64, batch_size=3,
                          moe_executor="dense")
    for p in prompts:
        dense.submit(p, max_new_tokens=6)
    dense.run()
    grouped = ServingEngine(model, params, max_len=64, batch_size=3,
                            moe_executor="grouped")
    for p in prompts:
        grouped.submit(p, max_new_tokens=6)
    grouped.run()
    assert dense.telemetry.dropped_matrix().sum() > 0
    assert grouped.telemetry.dropped_matrix().sum() == 0.0


def test_drop_ledger_survives_padded_expert_axis():
    """REGRESSION: a Model built with expert_pad_multiple > 1 routes over
    a padded expert axis; the RoutingSummary rows span E_pad but the
    telemetry ledger is sized by the real expert count — ingestion must
    slice, not broadcast-crash (pad experts never receive tokens)."""
    from repro.models import Model
    cfg, _ = tiny_model("gpt2-moe", capacity_factor=0.5)
    model = Model(cfg, expert_pad_multiple=8)   # E=4 -> E_pad=8
    assert model.num_experts_padded > cfg.moe.num_experts
    params = model.init_params(jax.random.PRNGKey(0))
    eng = ServingEngine(model, params, max_len=64, batch_size=2,
                        moe_executor="dense")
    for p in _prompts(cfg, [56, 50]):
        eng.submit(p, max_new_tokens=3)
    eng.run()
    ledger = eng.telemetry.dropped_matrix()
    assert ledger.shape == (cfg.num_layers, cfg.moe.num_experts)
    assert ledger.sum() > 0


def test_decode_telemetry_counts(gpt2_moe):
    """Every decoded token contributes top_k routings per MoE layer."""
    cfg, model, params = gpt2_moe
    prompts = _prompts(cfg, [4, 6])
    eng = ServingEngine(model, params, max_len=32, batch_size=2)
    reqs = [eng.submit(p, max_new_tokens=5) for p in prompts]
    eng.run()
    tel = eng.telemetry
    k = cfg.moe.top_k
    n_prompt = sum(len(p) for p in prompts)
    # first token of each request comes from prefill; the rest are decoded
    n_decode = sum(len(r.output) - 1 for r in reqs)
    assert tel.prefill_tokens == n_prompt
    assert tel.decode_tokens == n_decode
    assert tel.demand.sum() == (n_prompt + n_decode) * cfg.num_layers * k


def test_telemetry_folds_a_step_in_while_the_next_one_runs(gpt2_moe):
    """A decode step's captures are folded in once the next decode step
    is dispatched, or when the telemetry is read. The records and the
    demand then equal, in order, those of an engine whose telemetry is
    read (so folded) after every step, with a request admitted between
    steps and a slot taken over by the next request."""
    cfg, model, params = gpt2_moe
    prompts = _prompts(cfg, [4, 6, 5], seed=4)
    news = (2, 5, 3)
    engines = [ServingEngine(model, params, max_len=32, batch_size=2)
               for _ in range(2)]
    for eng in engines:
        for p, n in zip(prompts, news):
            eng.submit(p, max_new_tokens=n)
    lazy, eager = engines
    raw = lazy._telemetry
    assert lazy.step() and eager.step()
    eager.telemetry
    # the step's admissions are in, its decode step is not yet
    assert raw.prefill_tokens == 10 and raw.decode_tokens == 0
    assert lazy.step() and eager.step()
    eager.telemetry
    assert raw.decode_tokens == 2
    while lazy.step():
        eager.step()
        eager.telemetry
    assert not eager.step()
    tel, want = lazy.telemetry, eager.telemetry
    assert tel.decode_tokens == sum(n - 1 for n in news)
    np.testing.assert_array_equal(tel.demand, want.demand)
    assert len(tel._records) == len(want._records)
    for a, b in zip(tel._records, want._records):
        assert a.layer == b.layer
        for f in ("token_id", "position", "attention_id", "experts",
                  "weights"):
            np.testing.assert_array_equal(getattr(a, f), getattr(b, f))


def test_decode_attention_ids_are_the_attended_tokens():
    """A decode record's attention ID (f3) of block ``b`` and slot ``i`` is
    the token of that slot's history at the position it attended most
    (``attn_argmax`` less the frontend tokens, clipped to the history),
    for every block, slot, offset and clip."""
    from repro.serving.telemetry import ExpertTelemetry

    rng = np.random.default_rng(3)
    nb, slots, k, vocab, n_front = 4, 6, 2, 50, 2
    seqs = [rng.integers(0, vocab, int(n)) for n in rng.integers(1, 20, slots)]
    am = rng.integers(-3, 25, (nb, slots, 1))
    caps = {"pos0": {"topk_idx": rng.integers(0, 8, (nb, slots, 1, k)),
                     "topk_weight": rng.random((nb, slots, 1, k)),
                     "attn_argmax": am}}
    active = [0, 2, 3, 5]
    tel = ExpertTelemetry(nb, 8, vocab, 1)
    tel.record_decode(rng.integers(0, vocab, slots), np.zeros(slots, int),
                      seqs, caps, active, n_front=n_front)
    assert [r.layer for r in tel._records] == list(range(nb))
    for b, rec in enumerate(tel._records):
        want = [seqs[i][min(max(int(am[b, i, 0]) - n_front, 0),
                            len(seqs[i]) - 1)] for i in active]
        np.testing.assert_array_equal(rec.attention_id, want)
        assert rec.attention_id.dtype == np.int64


def test_engine_samples_the_argmax_of_its_decode_logits(gpt2_moe):
    """Each decoded token is the first largest logit of its slot's row, as
    numpy's argmax of the decode step's logits picks it."""
    cfg, model, params = gpt2_moe
    eng = ServingEngine(model, params, max_len=32, batch_size=2)
    steps = []
    decode = eng._jit_decode

    def spy(*args):
        out = decode(*args)
        steps.append(np.asarray(out[0]))
        return out

    eng._jit_decode = spy
    reqs = [eng.submit(p, max_new_tokens=4)
            for p in _prompts(cfg, [5, 3], seed=9)]
    eng.run()
    assert len(steps) == 3
    for n, logits in enumerate(steps):
        for req in reqs:
            assert req.output[1 + n] == int(logits[req.slot].argmax())
    ties = jnp.asarray([[1.0, 3.0, 3.0], [jnp.nan, 2.0, jnp.nan]])
    np.testing.assert_array_equal(np.asarray(eng._jit_greedy(ties)),
                                  np.asarray(ties).argmax(-1))


def test_plan_from_telemetry():
    """The runtime re-plans deployment from live serving traffic."""
    from repro.core.runtime import RuntimeConfig, ServerlessMoERuntime

    rc = RuntimeConfig(arch="gpt2-moe", d_model_reduced=64,
                       vocab_reduced=512, seq_len=12, batch_size=4,
                       profile_batches=1, learn_batches=1, eval_batches=1)
    rt = ServerlessMoERuntime(rc)
    eng = ServingEngine(rt.model, rt.params, max_len=32, batch_size=2)
    for row in next(rt.corpus.batches(1))["tokens"]:
        eng.submit(row, max_new_tokens=4)
    eng.run()
    policy = rt.plan_from_telemetry(eng.telemetry)
    assert policy.replicas.shape == (rt.num_layers, rt.num_experts)
    assert (policy.replicas >= 1).all()
    # the ingested table now carries the served traffic
    assert rt.table.demand_matrix().sum() >= eng.telemetry.demand.sum()


# ------------------------------------------------------ speculative dispatch
def test_speculative_dispatch_emits_and_scores_prewarm_hints(gpt2_moe):
    """With an OnlinePredictor attached, every decode step emits per-layer
    prewarm hints BEFORE routing runs, scores them against the realized
    routing, and streams the step's observations back into the predictor."""
    from repro.predict import OnlinePredictor, uniform_hit_rate

    cfg, model, params = gpt2_moe
    E = cfg.moe.num_experts
    pred = OnlinePredictor(cfg.num_layers, E, cfg.vocab_size,
                           top_k=cfg.moe.top_k, decay=0.99)
    eng = ServingEngine(model, params, max_len=32, batch_size=2,
                        predictor=pred)
    for p in _prompts(cfg, [5, 7, 4], seed=3):
        eng.submit(p, max_new_tokens=6)
    eng.run()
    tel = eng.telemetry
    stats = eng.speculation_stats()
    assert stats["pairs"] > 0
    assert stats["hits"] + 0 <= stats["pairs"]
    assert stats["hit_rate"] is not None and 0.0 <= stats["hit_rate"] <= 1.0
    assert len(stats["per_layer_hit_rate"]) == cfg.num_layers
    # hints were emitted with the model's geometry
    assert eng.last_prewarm_hints is not None
    assert eng.last_prewarm_hints.shape == (cfg.num_layers, E)
    assert eng.last_prewarm_hints.dtype == bool
    # the predictor learned online from both prefill and decode records
    assert pred.updates > 0 and pred.num_statistics > 0
    # reset clears the scoreboard
    tel.reset()
    assert tel.prewarm_pairs == 0 and tel.prewarm_hit_rate() is None


def test_speculation_learns_toward_routing(gpt2_moe):
    """Served traffic trains the predictor: after serving, its MAP demand
    on the served stream must beat the uniform prior's hit rate against
    the telemetry's realized routing."""
    from repro.predict import OnlinePredictor, topk_hit_rate, uniform_hit_rate

    cfg, model, params = gpt2_moe
    E = cfg.moe.num_experts
    pred = OnlinePredictor(cfg.num_layers, E, cfg.vocab_size,
                           top_k=cfg.moe.top_k)
    eng = ServingEngine(model, params, max_len=32, batch_size=2,
                        predictor=pred)
    rng = np.random.default_rng(5)
    for _ in range(4):
        eng.submit(rng.integers(0, cfg.vocab_size, size=6), max_new_tokens=6)
    eng.run()
    recs = eng.telemetry._records
    assert recs, "telemetry must retain records for calibration"
    rate = topk_hit_rate(pred, recs, k=cfg.moe.top_k)
    # the predictor SAW these records (in-sample): it must beat uniform
    assert rate > uniform_hit_rate(E, cfg.moe.top_k)


def test_predictor_without_telemetry_is_rejected(gpt2_moe):
    from repro.predict import OnlinePredictor

    cfg, model, params = gpt2_moe
    pred = OnlinePredictor(cfg.num_layers, cfg.moe.num_experts,
                           cfg.vocab_size)
    with pytest.raises(ValueError, match="telemetry"):
        ServingEngine(model, params, max_len=32, batch_size=1,
                      collect_telemetry=False, predictor=pred)


# ------------------------------------------------------------- kernel paths
def _serve(model, params, prompts, **kw):
    eng = ServingEngine(model, params, max_len=32, batch_size=len(prompts),
                        collect_telemetry=False, **kw)
    reqs = [eng.submit(p, max_new_tokens=5) for p in prompts]
    eng.run()
    return eng, [r.output for r in reqs]


@pytest.mark.parametrize("kernels", ["fused", "pallas"])
def test_engine_kernel_paths_match_reference(gpt2_moe, kernels):
    """The fused-routing/flash-decode hot paths must reproduce the
    reference engine's outputs token-for-token: fused routing is
    bit-equal routing-wise, and the ragged kv_len bound only excludes
    cache rows that decode validity already masked."""
    cfg, model, params = gpt2_moe
    prompts = _prompts(cfg, [3, 7, 5], seed=6)
    _, ref = _serve(model, params, prompts, kernels="reference")
    _, got = _serve(model, params, prompts, kernels=kernels)
    assert got == ref


def test_kv_len_bucket_is_output_invariant(gpt2_moe):
    """The bucketed static kv_len only bounds how much padded cache the
    decode step reads; any bucket size must yield identical outputs."""
    cfg, model, params = gpt2_moe
    prompts = _prompts(cfg, [4, 9], seed=7)
    outs = [_serve(model, params, prompts, kernels="fused",
                   kv_len_bucket=b)[1] for b in (1, 4, 32)]
    assert outs[0] == outs[1] == outs[2]


@pytest.mark.parametrize("kernels,telemetry", [("turbo", False),
                                               ("pallas", True)])
def test_unknown_engine_kernels_rejected(gpt2_moe, kernels, telemetry):
    """An unknown kernel path is refused, and so is the flash-decode path
    with telemetry on: the kernel emits no attention argmax to capture."""
    cfg, model, params = gpt2_moe
    with pytest.raises(ValueError, match="kernels"):
        ServingEngine(model, params, max_len=32, batch_size=1,
                      collect_telemetry=telemetry, kernels=kernels)


# ------------------------------------------------------------- prefix cache
def test_prefix_cache_exact_hit_is_bit_identical(gpt2_moe):
    """A repeated prompt is served from the stored prepared cache + last
    logits without re-prefilling, and the outputs match the uncached
    engine exactly (prefill is deterministic)."""
    cfg, model, params = gpt2_moe
    (prompt,) = _prompts(cfg, [6], seed=8)
    prompts = [prompt, prompt.copy(), prompt.copy()]
    _, ref = _serve(model, params, prompts)
    eng, got = _serve(model, params, prompts, prefix_cache_size=4)
    assert got == ref
    st = eng.prefix_cache.stats()
    assert st["exact_hits"] == 2 and st["misses"] == 1
    assert st["saved_tokens"] == 2 * len(prompt)


def test_prefix_cache_exact_hit_replays_telemetry():
    """With telemetry on, an exact hit replays the stored sliced prefill
    captures: the demand matrix equals the uncached engine's."""
    cfg, model = tiny_model("gpt2-moe")
    params = model.init_params(jax.random.PRNGKey(0))
    (prompt,) = _prompts(cfg, [6], seed=9)

    def run(**kw):
        eng = ServingEngine(model, params, max_len=32, batch_size=2, **kw)
        reqs = [eng.submit(prompt.copy(), max_new_tokens=4)
                for _ in range(2)]
        eng.run()
        return eng, [r.output for r in reqs]

    ref_eng, ref = run()
    hit_eng, got = run(prefix_cache_size=4)
    assert got == ref
    assert hit_eng.prefix_cache.stats()["exact_hits"] == 1
    np.testing.assert_array_equal(hit_eng.telemetry.demand_matrix(),
                                  ref_eng.telemetry.demand_matrix())


def test_prefix_cache_extends_shared_prefix(gpt2_moe):
    """A stored prompt that is a strict prefix of a new one seeds its
    cache: only the unseen suffix is teacher-forced, and the outputs
    still match the uncached engine token-for-token."""
    cfg, model, params = gpt2_moe
    (long_p,) = _prompts(cfg, [11], seed=10)
    short_p = long_p[:6].copy()
    prompts = [short_p, long_p]
    _, ref = _serve(model, params, prompts)
    eng, got = _serve(model, params, prompts, prefix_cache_size=4)
    assert got == ref
    st = eng.prefix_cache.stats()
    assert st["prefix_hits"] == 1
    assert st["saved_tokens"] == len(short_p)


def test_prefix_cache_rejected_for_encoder_decoder():
    """Prefix reuse rests on causal decoder-only KV semantics; the
    engine must refuse to enable it elsewhere."""
    cfg, model = tiny_model("whisper-small")
    params = model.init_params(jax.random.PRNGKey(0))
    with pytest.raises(ValueError, match="prefix cache"):
        ServingEngine(model, params, max_len=32, batch_size=1,
                      collect_telemetry=False, prefix_cache_size=4)

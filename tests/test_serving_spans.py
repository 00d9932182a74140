"""The serving engine's host spans and the model's name scopes, read back
from a profiler trace recorded on the CPU, and the engine's pair of KV
row counters against a hand count."""
import glob
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro.serving import ServingEngine

from conftest import tiny_model

SPANS = ("serving.step", "serving.admit", "serving.prefill",
         "serving.kv_insert", "serving.decode", "serving.device_wait",
         "serving.telemetry", "serving.sample")
# prompt lengths and outputs of the three requests: 3, 1 and 2 decode
# steps after each prefill's first token
LENGTHS, NEW = (3, 7, 5), (4, 2, 3)


@pytest.fixture(scope="module")
def gpt2_moe():
    cfg, model = tiny_model("gpt2-moe")
    return cfg, model, model.init_params(jax.random.PRNGKey(0))


def _serve(gpt2_moe):
    cfg, model, params = gpt2_moe
    rng = np.random.default_rng(3)
    eng = ServingEngine(model, params, max_len=32, batch_size=4,
                        kv_len_bucket=4)
    reqs = [eng.submit(rng.integers(0, cfg.vocab_size, n).astype(np.int32),
                       max_new_tokens=k) for n, k in zip(LENGTHS, NEW)]
    while eng.step():
        pass
    return eng, reqs


@pytest.fixture(scope="module")
def traced(gpt2_moe, tmp_path_factory):
    """One run of the engine under the profiler: the engine, its requests
    and the trace's ``serving.`` events per host line, as
    (name, start_ns, end_ns, stats)."""
    d = str(tmp_path_factory.mktemp("trace"))
    with jax.profiler.trace(d):
        eng, reqs = _serve(gpt2_moe)
    pd = ProfileData.from_file(
        sorted(glob.glob(f"{d}/**/*.xplane.pb", recursive=True))[-1])
    lines = []
    for plane in pd.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                evs = [(e.name, e.start_ns, e.start_ns + e.duration_ns,
                        dict(e.stats)) for e in line.events
                       if e.name.startswith("serving.")]
                if evs:
                    lines.append(evs)
    return eng, reqs, lines


def _inside(inner, outer):
    return outer[1] <= inner[1] and inner[2] <= outer[2]


def test_every_span_is_in_the_trace(traced):
    _, _, lines = traced
    names = {e[0] for evs in lines for e in evs}
    assert names == set(SPANS)


def test_admission_spans_nest(traced):
    """``serving.prefill`` and ``serving.kv_insert`` lie inside an
    ``serving.admit``, and each admission inside a ``serving.step``."""
    _, reqs, lines = traced
    evs = [e for line in lines for e in line]
    by = {n: [e for e in evs if e[0] == n] for n in SPANS}
    assert len(by["serving.admit"]) == len(reqs)
    for name in ("serving.prefill", "serving.kv_insert"):
        assert len(by[name]) == len(reqs)
        for e in by[name]:
            assert any(_inside(e, a) for a in by["serving.admit"])
    for a in by["serving.admit"]:
        assert any(_inside(a, s) for s in by["serving.step"])
    # outside the admissions, a decoding step dispatches, waits, then
    # samples
    decode = ("serving.decode", "serving.device_wait", "serving.sample")
    for s in by["serving.step"]:
        inner = sorted((e for e in evs if e[0] in decode and _inside(e, s)
                        and not any(_inside(e, a)
                                    for a in by["serving.admit"])),
                       key=lambda e: e[1])
        assert [e[0] for e in inner] in ([], list(decode))


def test_span_args(traced):
    """The admission span names its request, prompt length and lookup;
    the step span its number, live slots and the rows read per slot."""
    _, reqs, lines = traced
    evs = [e for line in lines for e in line]
    admits = sorted((e for e in evs if e[0] == "serving.admit"),
                    key=lambda e: e[1])
    assert [e[3]["uid"] for e in admits] == [r.uid for r in reqs]
    assert [e[3]["prompt_len"] for e in admits] == list(LENGTHS)
    assert {e[3]["kind"] for e in admits} == {"miss"}
    steps = sorted((e for e in evs if e[0] == "serving.step"),
                   key=lambda e: e[1])
    decoding = [e[3] for e in steps if "live" in e[3]]
    # lengths after each step's write: 4, 8, 6; then 5, 7; then 6
    assert [(s["step_num"], s["live"], s["kv_len"]) for s in decoding] == [
        (0, 3, 8), (1, 2, 8), (2, 1, 8)]


def test_profiler_changes_no_token_or_logit(gpt2_moe, traced, tmp_path):
    eng_on, reqs_on, _ = traced
    eng_off, reqs_off = _serve(gpt2_moe)
    assert [r.output for r in reqs_on] == [r.output for r in reqs_off]
    toks = jnp.asarray(np.arange(4, dtype=np.int32)[:, None])
    pos = jnp.asarray(np.array([3, 5, 0, 1], np.int32))

    def logits(eng):
        out, _, _ = eng._jit_decode(eng.params, toks,
                                    jax.tree.map(jnp.copy, eng.kv.cache),
                                    pos, None, 8)
        return np.asarray(out)

    with jax.profiler.trace(str(tmp_path)):
        on = logits(eng_on)
    np.testing.assert_array_equal(on, logits(eng_off))


def test_decode_program_carries_the_model_scopes(gpt2_moe):
    cfg, model, params = gpt2_moe
    eng = ServingEngine(model, params, max_len=32, batch_size=4)
    toks = jnp.zeros((4, 1), jnp.int32)
    pos = jnp.zeros(4, jnp.int32)
    text = eng._jit_decode.lower(params, toks, eng.kv.cache, pos, None,
                                 16).compile().as_text()
    names = set(re.findall(r'op_name="([^"]*)"', text))
    for scope in ("embed/", "attention/", "attention/kv_write/",
                  "moe/router/", "moe/dispatch/", "moe/experts/",
                  "moe/combine/", "head/"):
        assert any(scope in n for n in names), scope


def test_kv_row_counters_match_a_hand_count(traced):
    """Four slots, bucket 4. Step 0 decodes all three requests at
    positions 3, 7, 5 (rows after the write 4 + 8 + 6, bound 8); step 1
    the first and third at 4, 6 (5 + 7); step 2 the first at 5 (6). Each
    step reads 4 slots x 8 rows."""
    eng, _, _ = traced
    assert eng.kv_rows_read == 3 * 4 * 8
    assert eng.kv_rows_live == (4 + 8 + 6) + (5 + 7) + 6

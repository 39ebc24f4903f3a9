"""Continuous-batching engine tests: correctness vs sequential decode,
admission of new requests mid-flight, slot reuse, determinism (greedy ->
CAS-publishable), and multi-tenant interleave."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.models.transformer import build_model
from repro.serve.engine import Request, ServingEngine


@pytest.fixture(scope="module")
def served():
    cfg = get_config("smollm-135m").reduced(n_layers=2, d_model=64,
                                            vocab_size=128, d_ff=128)
    model = build_model(cfg)
    params = model.init(jax.random.key(0))
    return cfg, model, params


def greedy_reference(model, params, prompt, n_new):
    """Sequential single-request decode (oracle)."""
    cache = model.init_cache(1, 512)
    logits, cache = model.prefill(
        params, {"tokens": jnp.asarray(prompt)[None, :]}, cache)
    out = [int(jnp.argmax(logits[0, -1]))]
    for _ in range(n_new - 1):
        logits, cache = model.decode(
            params, jnp.asarray([[out[-1]]], jnp.int32), cache)
        out.append(int(jnp.argmax(logits[0, -1])))
    return out


def test_batched_equals_sequential(served):
    cfg, model, params = served
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, cfg.vocab_size, size=n).astype(np.int32)
               for n in (5, 9, 13)]
    refs = [greedy_reference(model, params, p, 6) for p in prompts]
    eng = ServingEngine(model, params, n_slots=4, max_len=512)
    done = eng.run([Request(p, max_new_tokens=6) for p in prompts])
    done.sort(key=lambda r: r.req_id)
    for req, ref in zip(done, refs):
        assert req.generated == ref, \
            f"continuous batching diverged: {req.generated} vs {ref}"


def test_admission_mid_flight(served):
    cfg, model, params = served
    rng = np.random.default_rng(1)
    eng = ServingEngine(model, params, n_slots=2, max_len=256)
    r1 = Request(rng.integers(0, 128, 7).astype(np.int32), max_new_tokens=12)
    r2 = Request(rng.integers(0, 128, 5).astype(np.int32), max_new_tokens=12)
    eng.submit(r1)
    eng.submit(r2)
    eng.step()
    # both slots busy; a third tenant's request arrives mid-decode
    r3 = Request(rng.integers(0, 128, 4).astype(np.int32),
                 max_new_tokens=4, tenant="tenant-B")
    eng.submit(r3)
    done = []
    while eng.waiting or eng.active:
        done.extend(eng.step())
    assert {r.req_id for r in done} == {r1.req_id, r2.req_id, r3.req_id}
    # r3 was admitted into a slot freed mid-run (continuous batching)
    ref3 = greedy_reference(model, params, r3.prompt, 4)
    assert done[-1].generated == ref3 or \
        [r for r in done if r.req_id == r3.req_id][0].generated == ref3


def test_slot_reuse_many_requests(served):
    cfg, model, params = served
    rng = np.random.default_rng(2)
    eng = ServingEngine(model, params, n_slots=2, max_len=128)
    reqs = [Request(rng.integers(0, 128, 4 + i % 3).astype(np.int32),
                    max_new_tokens=3) for i in range(7)]
    done = eng.run(reqs)
    assert len(done) == 7
    assert len(eng.free_slots) == 2          # all slots returned
    # verify each against the oracle
    for r in done:
        assert r.generated == greedy_reference(model, params, r.prompt, 3)


def test_greedy_is_deterministic(served):
    cfg, model, params = served
    rng = np.random.default_rng(3)
    p = rng.integers(0, 128, 6).astype(np.int32)

    def once():
        eng = ServingEngine(model, params, n_slots=2, max_len=128)
        return eng.run([Request(p.copy(), max_new_tokens=5)])[0].generated

    assert once() == once()      # deterministic -> publishable by content hash


# ----------------------------------------------------- counters and spans --
def test_counters_after_a_closed_batch(served):
    """``stats()`` after a closed batch: one prefill per request, their
    prompt tokens, and a host read per sampled token plus one per length
    check; the tokens still equal the sequential oracle's."""
    cfg, model, params = served
    rng = np.random.default_rng(4)
    prompts = [rng.integers(0, cfg.vocab_size, size=n).astype(np.int32)
               for n in (5, 9, 13)]
    n_new = (6, 3, 5)
    eng = ServingEngine(model, params, n_slots=2, max_len=256)
    done = eng.run([Request(p, max_new_tokens=k)
                    for p, k in zip(prompts, n_new)])
    done.sort(key=lambda r: r.req_id)
    for req, p, k in zip(done, prompts, n_new):
        assert req.generated == greedy_reference(model, params, p, k)
    s = eng.stats()
    assert s["prefills"] == len(prompts)
    assert s["prompt_tokens"] == sum(len(p) for p in prompts)
    tokens = sum(len(r.generated) for r in done)
    assert s["tokens_generated"] + s["prefills"] == tokens
    # the first token comes from prefill; a decode token is length-checked
    # unless it is the request's last
    length_reads = sum(len(r.generated) - 2 for r in done)
    assert s["host_reads"] == tokens + length_reads
    assert s["steps"] == eng.steps > 0
    assert s["prefill_s"] > 0 and s["decode_s"] > 0


def test_compiles_count_a_new_prompt_length_once(served):
    cfg, model, params = served
    rng = np.random.default_rng(5)
    eng = ServingEngine(model, params, n_slots=2, max_len=128)

    def serve(n):
        before = eng.compiles
        eng.run([Request(rng.integers(0, 128, n).astype(np.int32),
                         max_new_tokens=3)])
        return eng.compiles - before

    assert serve(5) > 0              # the prefill, decode and their reads
    assert serve(11) == 1            # only the new length's prefill
    assert serve(11) == 0
    assert serve(5) == 0
    assert eng.stats()["compiles"] == eng.compiles


def _host_spans(log_dir) -> list[tuple[int, int, str, dict]]:
    from jax.profiler import ProfileData
    paths = list(log_dir.rglob("*.xplane.pb"))
    assert len(paths) == 1
    out = []
    for plane in ProfileData.from_file(str(paths[0])).planes:
        if not plane.name.startswith("/host:CPU"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith("engine."):
                    out.append((e.start_ns, e.start_ns + e.duration_ns,
                                e.name, dict(e.stats)))
    return out


def test_profiler_trace_nests_the_engine_spans(served, tmp_path):
    """With a trace on, each step's decode, sampling and retirement spans
    lie inside its ``engine.step``, each prefill inside an
    ``engine.admit``, with the request's id, length and slot."""
    cfg, model, params = served
    rng = np.random.default_rng(6)
    eng = ServingEngine(model, params, n_slots=2, max_len=128)
    eng.run([Request(rng.integers(0, 128, 4).astype(np.int32),
                     max_new_tokens=2)])                 # warm-up
    reqs = [Request(rng.integers(0, 128, 4).astype(np.int32),
                    max_new_tokens=3) for _ in range(2)]
    steps = eng.steps
    jax.profiler.start_trace(str(tmp_path))
    try:
        eng.run(reqs)
    finally:
        jax.profiler.stop_trace()
    spans = _host_spans(tmp_path)
    by = {}
    for s in spans:
        by.setdefault(s[2], []).append(s)

    def inside(span, parent):
        return any(p[0] <= span[0] and span[1] <= p[1] for p in by[parent])

    assert len(by["engine.step"]) == eng.steps - steps
    for name in ("engine.decode", "engine.sample", "engine.retire",
                 "engine.admit"):
        assert by[name] and all(inside(s, "engine.step") for s in by[name])
    assert all(inside(s, "engine.admit") for s in by["engine.prefill"])
    ids = {r.req_id for r in reqs}
    assert {s[3]["req_id"] for s in by["engine.prefill"]} == ids
    assert {(s[3]["tokens"], s[3]["slot"]) for s in by["engine.prefill"]} \
        == {(4, 0), (4, 1)}
    assert {s[3]["req_id"] for s in by["engine.retire"]} == ids
    # one sample span per host token read, one retire span per decode token
    assert len(by["engine.sample"]) == sum(len(r.generated) for r in reqs)
    assert len(by["engine.retire"]) == sum(len(r.generated) - 1
                                           for r in reqs)

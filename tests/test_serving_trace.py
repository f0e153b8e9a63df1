"""Serving spans: ``step_once`` opens ``serve.*`` spans at its layer
boundaries (in-memory when the tracer is enabled, as profiler
annotations always), requests carry their admission time, and the
jitted programs have stable names."""

import glob
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import ArchConfig
from repro.models import init_params
from repro.obs import trace as obs_trace
from repro.serving import ContinuousBatchingServer, Request

TINY = ArchConfig(name="tiny", family="dense", num_layers=2, d_model=32,
                  num_heads=4, num_kv_heads=2, d_ff=64, vocab_size=64,
                  dtype="float32")
PROMPT = np.arange(8, dtype=np.int32) % TINY.vocab_size   # 2 full blocks


@pytest.fixture(scope="module")
def tiny_params():
    return init_params(jax.random.PRNGKey(0), TINY)


@pytest.fixture
def tracer():
    t = obs_trace.Tracer(enabled=True)
    prev = obs_trace.set_tracer(t)
    yield t
    obs_trace.set_tracer(prev)


def _server(params, **kw):
    kw = dict(dict(batch_size=2, max_len=32, block_size=4,
                   prefill_chunk=8), **kw)
    return ContinuousBatchingServer(TINY, params, **kw)


def _req(rid, prompt=PROMPT, max_new=3):
    return Request(rid=rid, prompt=prompt.copy(), max_new_tokens=max_new)


def _serve_with_cow(server):
    """rid 0 alone, then rid 1 (the same prompt: a full prefix-cache hit,
    so a copy-on-write) beside rid 2 (a longer prompt of its own)."""
    server.submit(_req(0))
    server.run()
    server.submit(_req(1))
    server.submit(_req(2, prompt=np.arange(20, 32, dtype=np.int32)))
    return server.run()


def test_step_spans_nest_in_serve_step(tiny_params, tracer):
    server = _server(tiny_params)
    _serve_with_cow(server)
    spans = tracer.spans
    assert spans and all(s.cat == obs_trace.CAT_SERVE for s in spans)
    by_id = {s.span_id: s for s in spans}
    steps = [s for s in spans if s.name == "serve.step"]
    # a step that finds nothing active returns before counting itself
    index = [s.args["step"] for s in steps]
    assert index[0] == 0 and index == sorted(index)
    assert set(index) == set(range(server._step + 1))
    assert all(s.parent_id is None for s in steps)
    names = {s.name for s in spans}
    assert names == {"serve.step", "serve.schedule", "serve.cow",
                     "serve.prefill", "serve.decode.batch", "serve.decode",
                     "serve.sample", "serve.wait", "serve.append"}
    for s in spans:
        if s.name != "serve.step":
            assert by_id[s.parent_id].name == "serve.step", s.name
            parent = by_id[s.parent_id]
            assert parent.t0 <= s.t0 and s.t0 + s.dur <= parent.t0 + \
                parent.dur + 1e-9
    # counters: one per prefill chunk, the prompt's rid and length
    pre = [s.args for s in spans if s.name == "serve.prefill"]
    assert sorted({a["rid"] for a in pre}) == [0, 1, 2]
    assert {(a["rid"], a["tokens"], a["ctx"]) for a in pre} >= {
        (0, 8, 0), (1, 1, 7), (2, 8, 0), (2, 4, 8)}
    (cow,) = [s for s in spans if s.name == "serve.cow"]
    assert cow.args == {"blocks": 1}
    for s in spans:
        if s.name == "serve.decode":
            assert s.args["batch"] == 2
            assert 1 <= s.args["rows"] <= 2
    # every sample is waited on once, then its tokens appended
    seq = [s.name for s in spans if s.name in ("serve.sample", "serve.wait",
                                               "serve.append")]
    assert seq == ["serve.sample", "serve.wait", "serve.append"] * \
        (len(seq) // 3)
    decode_rows = [s.args["rows"] for s in spans
                   if s.name == "serve.decode.batch"]
    assert sum(decode_rows) + 3 == sum(
        s.args["tokens"] for s in spans if s.name == "serve.append")


def test_spans_off_record_nothing(tiny_params):
    t = obs_trace.Tracer(enabled=False)
    prev = obs_trace.set_tracer(t)
    try:
        out = _serve_with_cow(_server(tiny_params))
    finally:
        obs_trace.set_tracer(prev)
    assert t.spans == []
    assert sorted(out) == [1, 2] and all(len(v) == 3 for v in out.values())


def test_profiler_trace_holds_serve_spans(tiny_params, tmp_path):
    from jax.profiler import ProfileData

    server = _server(tiny_params)
    server.submit(_req(0, max_new=8))
    server.step_once()                  # prefill, first token, a decode
    server.step_once()
    jax.profiler.start_trace(str(tmp_path))
    try:
        server.step_once()
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"),
                        recursive=True)
    events = [e for p in ProfileData.from_file(path).planes
              if p.name == "/host:CPU" for ln in p.lines for e in ln.events]
    stats = {e.name: dict(e.stats) for e in events
             if e.name.startswith("serve.")}
    assert stats["serve.step"] == {"step": 2}
    # the row decodes its 11th token: ceil(11 / 4) pages
    assert stats["serve.decode"] == {"rows": 1, "batch": 2, "kv_pages": 3}
    assert stats["serve.decode.batch"] == {"rows": 1}


def test_decode_span_counts_kernel_pages(tiny_params, tracer):
    """``kv_pages`` on ``serve.decode`` is the pages the decode kernel
    reads per layer: ceil((ctx + 1) / block_size) over running rows,
    read here from the context lengths the decode program is given."""
    server = _server(tiny_params, batch_size=3)
    seen = []
    decode = server._decode_fn

    def recording(params, pages, tokens, tables, ctx):
        seen.append(np.asarray(ctx))
        return decode(params, pages, tokens, tables, ctx)

    server._decode_fn = recording
    for rid, n in enumerate((3, 8, 13)):
        server.submit(_req(rid, prompt=np.arange(n, dtype=np.int32),
                           max_new=6))
    server.run()
    got = [s.args["kv_pages"] for s in tracer.spans
           if s.name == "serve.decode"]
    # a running row has prefilled its prompt, so ctx > 0; idle rows are 0
    want = [int(sum(-(-(c + 1) // 4) for c in ctx if c > 0))
            for ctx in seen]
    assert got == want and len(got) >= 5
    assert max(got) > min(got)


def test_admit_time_between_arrival_and_first_token(tiny_params):
    server = _server(tiny_params, batch_size=1)
    reqs = [_req(rid, prompt=PROMPT + rid) for rid in range(3)]
    for r in reqs:
        server.submit(r)
    server.run()
    for r in reqs:
        assert r.arrival_t <= r.admit_t <= r.first_token_t
    # one slot: each admission waits for the one before to finish
    assert reqs[0].admit_t < reqs[1].admit_t < reqs[2].admit_t
    assert reqs[1].admit_t >= reqs[0].finish_t


def _lowered(server, program):
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32)
    f32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.float32)
    b, m, v = server.batch, server.max_blocks_per_seq, TINY.vocab_size
    args = {
        "prefill": (server.params, server.pages, i32(1, 8), i32(1, m),
                    i32(1), i32(1)),
        "decode": (server.params, server.pages, i32(b, 1), i32(b, m),
                   i32(b)),
        "greedy": (f32(b, v),),
        "sample": (f32(b, v), i32(b), f32(b), i32(b)),
        "sample_notopk": (f32(b, v), i32(b), f32(b)),
        "copy": (server.pages, i32(2), i32(2)),
    }[program]
    return getattr(server, f"_{program}_fn").lower(*args).as_text()


@pytest.mark.parametrize("program, module", [
    ("decode", "jit_serve_decode"),
    ("prefill", "jit_serve_prefill"),
    ("greedy", "jit_serve_greedy"),
    ("sample", "jit_serve_sample"),
    ("sample_notopk", "jit_serve_sample_notopk"),
    ("copy", "jit_serve_copy_blocks"),
])
def test_program_module_names(tiny_params, program, module):
    text = _lowered(_server(tiny_params), program)
    assert re.search(r"module @(\S+)", text).group(1) == module

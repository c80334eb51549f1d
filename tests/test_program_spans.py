"""The runtime's own spans in a JAX profiler trace (``timeline.span``).

A small Black-Scholes program (3 stocks x 2 iterations: one upload, one
kernel and one host read per stock and iteration) runs on the real executor
under a profiler trace.  Its ``gr:`` spans must mirror the in-memory
``Timeline`` element for element, by ``uid``; the spans of one element must
nest as the code nests; lane work must sit on the lane threads, the early
copies of the outputs the host reads among it.  With no
trace running, nothing changes but the absence of the spans.
"""
import sys
from collections import Counter

import numpy as np
import pytest

from repro.benchsuite import kernels as K
from repro.core import make_scheduler
from repro.core import timeline as TL

STOCKS, ITERS, N = 3, 2, 1024
BODIES = ("h2d", "d2d", "evict", "reload", "kernel")


def bs_program(sched, data):
    """The B&S host code: upload each stock, price it, read every price."""
    for it in range(ITERS):
        outs = []
        for i, s in enumerate(data):
            x = sched.array(s + np.float32(it), name=f"s{i}_{it}")
            o = sched.array(shape=(N,), dtype=np.float32, name=f"c{i}_{it}")
            K.BLACK_SCHOLES(x, o, scheduler=sched)
            outs.append(o)
        for o in outs:
            np.asarray(o)
    sched.sync()


def make_data():
    rng = np.random.default_rng(0)
    return [rng.random(N, np.float32) * np.float32(100) + np.float32(20)
            for _ in range(STOCKS)]


def timeline_of_run(sched, data):
    """The Timeline spans one run of the program adds."""
    n0 = len(sched.timeline.spans)
    bs_program(sched, data)
    return sched.timeline.spans[n0:]


def read_spans(log_dir):
    """The ``gr:`` events of a trace: (name, start, end, line, stats)."""
    from jax.profiler import ProfileData

    (path,) = [str(p) for p in log_dir.rglob("*.xplane.pb")]
    out = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for i, line in enumerate(plane.lines):
            for e in line.events:
                if e.name.startswith(TL.SPAN_PREFIX):
                    out.append((e.name[len(TL.SPAN_PREFIX):], e.start_ns,
                                e.start_ns + e.duration_ns,
                                (plane.name, i), dict(e.stats)))
    return out


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    """One untraced and one traced run on one runtime, after a warm-up."""
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    log_dir = tmp_path_factory.mktemp("trace")
    data = make_data()
    with make_scheduler("parallel") as sched:
        bs_program(sched, data)                     # compiles
        plain = timeline_of_run(sched, data)
        jax.profiler.start_trace(str(log_dir), profiler_options=opts)
        try:
            spans = timeline_of_run(sched, data)
        finally:
            jax.profiler.stop_trace()
    return plain, spans, read_spans(log_dir)


def named(events, *names):
    return [e for e in events if e[0] in names]


def inside(inner, outer):
    """``inner`` lies within ``outer`` on the same thread."""
    return (inner[3] == outer[3] and outer[1] <= inner[1]
            and inner[2] <= outer[2])


def test_every_timeline_element_has_a_twin_with_its_uid(traced):
    _, spans, events = traced
    lane = {e[4]["uid"] for e in named(events, *BODIES)}
    waits = {e[4]["uid"] for e in named(events, "host_wait")
             if "uid" in e[4]}
    for s in spans:
        if s.uid < 0:           # a host read's copy: no element of its own
            continue
        assert s.uid in (waits if s.kind == "host" else lane), s
    copies = [s for s in spans if s.uid < 0]
    assert len(copies) == len(named(events, "d2h", "tier_restore"))


def test_one_span_per_upload_call_and_host_copy(traced):
    _, spans, events = traced
    counts = Counter(e[0] for e in events)
    uploads = [s for s in spans if s.kind == "h2d"]
    assert len(uploads) == counts["h2d"] == STOCKS * ITERS
    assert {s.uid for s in uploads} == {
        e[4]["uid"] for e in named(events, "h2d")}
    assert counts["launch"] == STOCKS * ITERS
    assert counts["kernel"] == STOCKS * ITERS
    host_copies = [s for s in spans if s.uid < 0 and s.kind == "d2h"]
    assert len(host_copies) == counts["d2h"] == STOCKS * ITERS
    assert all(e[4]["nbytes"] == N * 4 for e in named(events, "d2h", "h2d"))


def test_a_launch_links_to_the_elements_it_scheduled(traced):
    _, _, events = traced
    for launch in named(events, "launch"):
        scheduled = [e[4]["uid"] for e in named(events, "schedule")
                     if inside(e, launch)]
        # The upload of the stock, then the kernel the launch returned.
        assert len(scheduled) == 2
        assert scheduled[-1] == launch[4]["uid"]
        stages = {e[0] for e in events if inside(e, launch)}
        assert {"lock", "place", "reserve", "prefetch"} <= stages


def test_dispatch_and_block_nest_in_their_body_span(traced):
    _, _, events = traced
    bodies = named(events, *BODIES)
    children = named(events, "dispatch", "block")
    assert len(children) == 2 * len(bodies)
    for c in children:
        assert any(inside(c, b) for b in bodies), c


def test_host_wait_and_d2h_nest_in_the_host_read(traced):
    _, _, events = traced
    reads = named(events, "host_read")
    assert len(reads) == STOCKS * ITERS
    for c in named(events, "d2h"):
        assert any(inside(c, r) for r in reads), c
    syncs = named(events, "sync")
    for w in named(events, "host_wait"):
        assert any(inside(w, r) for r in reads + syncs), w
    assert any(inside(w, r) for w in named(events, "host_wait")
               for r in reads)


def test_read_ahead_copies_start_on_the_lane_inside_the_dispatch(traced):
    """The warm-up taught the read-ahead predictor that every price is read:
    each kernel starts its output's copy to the host as it is dispatched."""
    _, _, events = traced
    kernels = {e[4]["uid"]: e for e in named(events, "kernel")}
    ahead = named(events, "d2h_ahead")
    assert len(ahead) == STOCKS * ITERS
    launch_lines = {e[3] for e in named(events, "launch", "host_read")}
    for a in ahead:
        assert a[4]["nbytes"] == N * 4
        assert inside(a, kernels[a[4]["uid"]])
        assert any(inside(a, d) for d in named(events, "dispatch"))
        assert a[3] not in launch_lines


def test_a_read_served_by_an_early_copy_says_so(traced):
    _, _, events = traced
    d2h = named(events, "d2h")
    assert len(d2h) == STOCKS * ITERS
    assert all(e[4]["ahead"] == 1 for e in d2h)


def test_lane_spans_sit_on_other_threads_than_launches(traced):
    _, _, events = traced
    launch_lines = {e[3] for e in named(events, "launch", "host_read")}
    lane_lines = {e[3] for e in named(events, "lane_wait", *BODIES)}
    assert lane_lines and launch_lines
    assert not lane_lines & launch_lines


def test_without_a_trace_the_timeline_is_unchanged(traced):
    plain, spans, _ = traced
    assert TL.span("h2d", uid=1) is TL._NO_SPAN
    assert Counter((s.name, s.kind) for s in plain) == Counter(
        (s.name, s.kind) for s in spans)


def test_span_never_imports_jax(monkeypatch):
    """A process that has not loaded JAX (the simulator's) gets the no-op
    span, and the simulator's timeline is what ``record`` wrote."""
    monkeypatch.delitem(sys.modules, "jax.profiler", raising=False)
    assert TL.span("launch") is TL._NO_SPAN
    assert "jax.profiler" not in sys.modules
    with make_scheduler("parallel", simulate=True) as sched:
        x = sched.array(np.ones(8, np.float32), name="x")
        y = sched.array(shape=(8,), dtype=np.float32, name="y")
        K.BLACK_SCHOLES(x, y, scheduler=sched)
        sched.sync()
        assert [s.kind for s in sched.timeline.spans] == ["h2d", "compute"]
    assert "jax.profiler" not in sys.modules


# ----------------------------------------------------------------------
# The serving engine's batch kernel: prefill and decode spans, counters
PROMPT, NEW, BATCH = 12, 5, 2


def tiny_hybrid():
    """One Mamba block and one attention block: both kinds of state."""
    import dataclasses

    from repro.configs import get_config
    return dataclasses.replace(get_config("jamba2_3b", reduced=True),
                               n_layers=2, attn_period=2, attn_offset=1)


@pytest.fixture(scope="module")
def served(tmp_path_factory):
    """One warm-up batch, then one traced batch of the serving engine."""
    import jax

    from repro.models import init_lm
    from repro.runtime.serving import ServingEngine

    cfg = tiny_hybrid()
    params = init_lm(jax.random.PRNGKey(0), cfg)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    log_dir = tmp_path_factory.mktemp("serve_trace")
    rng = np.random.default_rng(0)
    with ServingEngine(cfg, params, batch_size=BATCH,
                       max_new_tokens=NEW) as eng:
        for traced in (False, True):
            for _ in range(BATCH):
                eng.submit(rng.integers(0, cfg.vocab, PROMPT,
                                        dtype=np.int32))
            if traced:
                jax.profiler.start_trace(str(log_dir), profiler_options=opts)
            try:
                eng.flush(force=True)
                eng.collect()
            finally:
                if traced:
                    jax.profiler.stop_trace()
        stats = eng.stats()
    return cfg, stats, read_spans(log_dir)


def test_a_served_batch_prefills_once_and_decodes_per_token_in_its_kernel(
        served):
    _, _, events = served
    (kernel,) = named(events, "kernel")
    (prefill,) = named(events, "prefill")
    decodes = named(events, "decode")
    assert inside(prefill, kernel)
    assert prefill[4]["batch"] == BATCH
    assert prefill[4]["tokens"] == BATCH * PROMPT
    assert len(decodes) == NEW - 1
    assert all(inside(d, kernel) and d[1] >= prefill[2] for d in decodes)
    assert [d[4]["pos"] for d in decodes] == list(range(PROMPT,
                                                        PROMPT + NEW - 1))


def test_serving_counters_match_the_cache_pytree(served):
    import jax

    from repro.models import init_cache
    cfg, stats, _ = served
    st = stats["serving"][(PROMPT, NEW)]
    assert st["prefill_tokens"] == 2 * BATCH * PROMPT        # two batches
    assert st["decode_steps"] == 2 * (NEW - 1)
    cache = init_cache(cfg, BATCH, PROMPT + NEW)
    kv = sum(a.nbytes for blk in cache for k, a in blk.items()
             if k in ("k", "v"))
    ssm = sum(a.nbytes for blk in cache if "mamba" in blk
              for a in jax.tree_util.tree_leaves(blk["mamba"]))
    assert kv > 0 and ssm > 0
    assert (st["kv_cache_bytes"], st["ssm_state_bytes"]) == (kv, ssm)

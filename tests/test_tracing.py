"""The one tracing module (``heat_tpu/utils/profiling.py``) and the names it
puts into the programs: host spans (off: the shared null object; on: nesting,
parents, requests across threads, events, the ring's bound), recording that
follows a ``jax.profiler`` session, the scopes and module names in the lowered
text of the train step, the decode programs and the Lloyd step, the kernels'
``name=``, and the spans the decode engine and ``KMeans.fit`` leave behind.
CPU, small."""

import re
import threading
from collections import Counter

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import heat_tpu as ht
from heat_tpu.core import pallas_kernels as pk
from heat_tpu.nn.transformer import TransformerLM, TransformerLMConfig
from heat_tpu.utils import profiling as prof

AXES = ("dp", "pp", "tp", "sp")
LM_SCOPES = ("embed", "cast", "pipeline", "attn.qkv", "attn.core", "attn.proj",
             "mlp", "head", "loss", "grad_psum", "optimizer")


@pytest.fixture
def recording():
    prof.clear()
    prof.enable()
    yield prof
    prof.disable()
    prof.clear()


def scopes_of(lowered):
    """(module name, every component of every op_name) of a lowering, with
    the AD wrappers (`transpose(jvp(x))`) peeled off."""
    txt = lowered.as_text(debug_info=True)
    parts = set()
    for name in re.findall(r'loc\("([^"]+)"', txt):
        for part in name.split("/"):
            while True:
                m = re.match(r"^\w+\((.*)\)$", part)
                if not m:
                    break
                part = m.group(1)
            parts.add(part)
    return re.search(r"module @(\w+)", txt).group(1), parts


def small_lm(shape=(1, 1, 1, 1), **kw):
    n = int(np.prod(shape))
    grid = ht.MeshGrid(shape, AXES, devices=jax.devices()[:n])
    cfg = TransformerLMConfig(vocab=64, d_model=32, n_heads=4, n_layers=2,
                              **kw)
    model = TransformerLM(grid, cfg)
    return model, model.init(0)


# ---------------------------------------------------------------------- #
# the module                                                             #
# ---------------------------------------------------------------------- #
def test_off_is_the_shared_null_object_and_nothing_is_recorded():
    prof.disable()
    prof.clear()
    assert not prof.recording()
    a = prof.span("x", k=1)
    assert a is prof.span("y") is prof.begin("z") is prof._NULL
    with a as sp:
        sp.event("e")
        sp.set(hit=True)
    a.end()
    assert prof.spans() == [] and prof.dropped() == 0


def test_on_records_nesting_parents_attrs_and_events(recording):
    with prof.span("outer", k=1) as o:
        with prof.span("inner") as i:
            i.event("tick")
            i.event("tock")
        o.set(hit=False)
    recs = {r.name: r for r in prof.spans()}
    assert [r.name for r in prof.spans()] == ["inner", "outer"]
    assert recs["outer"].parent_id == 0
    assert recs["inner"].parent_id == recs["outer"].id
    assert recs["outer"].attrs == {"k": 1, "hit": False}
    assert [n for n, _t in recs["inner"].events] == ["tick", "tock"]
    t = [recs["outer"].t0, recs["inner"].t0, recs["inner"].events[0][1],
         recs["inner"].events[1][1], recs["inner"].t1, recs["outer"].t1]
    assert t == sorted(t)
    assert recs["inner"].thread == threading.get_ident()
    with prof.span("next"):
        pass
    assert prof.spans()[-1].parent_id == 0      # the stack unwound


def test_a_request_spans_threads_and_shares_its_rid(recording):
    req = prof.begin("request", rid=7)
    queue = prof.begin("queue", parent=req, rid=7)

    def worker():
        queue.end()
        with prof.span("work", rid=7):
            req.event("token")
        req.end()
        req.end()                               # a second end does nothing

    th = threading.Thread(target=worker)
    th.start()
    th.join()
    recs = {r.name: r for r in prof.spans()}
    assert Counter(r.name for r in prof.spans()) == {
        "queue": 1, "work": 1, "request": 1}
    assert recs["queue"].parent_id == recs["request"].id
    assert recs["work"].parent_id == 0          # another thread's own stack
    assert {r.attrs["rid"] for r in recs.values()} == {7}
    assert recs["request"].thread == th.ident != threading.get_ident()
    assert len(recs["request"].events) == 1


def test_the_ring_is_bounded_and_counts_what_it_drops(recording, monkeypatch):
    import collections

    monkeypatch.setattr(prof, "_ring", collections.deque(maxlen=4))
    for i in range(10):
        with prof.span("s", i=i):
            pass
    assert [r.attrs["i"] for r in prof.spans()] == [6, 7, 8, 9]
    assert prof.dropped() == 6
    prof.clear()
    assert prof.spans() == [] and prof.dropped() == 0


def test_many_threads_lose_no_record(recording, monkeypatch):
    import collections
    import sys

    monkeypatch.setattr(prof, "_ring", collections.deque(maxlen=3000))
    n_threads, n_each = 16, 500                 # more workers than cores
    start = threading.Event()

    def worker(k):
        start.wait(10)
        for i in range(n_each):
            with prof.span("outer", k=k):
                with prof.span("inner", k=k, i=i):
                    pass

    was = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        threads = [threading.Thread(target=worker, args=(k,))
                   for k in range(n_threads)]
        for th in threads:
            th.start()
        start.set()
        for th in threads:
            th.join(60)
        assert not any(th.is_alive() for th in threads)
    finally:
        sys.setswitchinterval(was)
    recs = prof.spans()
    # every record is either in the ring or counted as dropped
    assert len(recs) + prof.dropped() == 2 * n_threads * n_each
    assert len(recs) == 3000 and len({r.id for r in recs}) == 3000
    outer = {r.id: r for r in recs if r.name == "outer"}
    for r in recs:                              # each thread's own stack
        if r.name == "inner" and r.parent_id in outer:
            assert outer[r.parent_id].attrs["k"] == r.attrs["k"]
            assert outer[r.parent_id].thread == r.thread


def test_recording_follows_a_profiler_session(tmp_path):
    """The one place that reads jax's private session state: a JAX that
    moves it fails here, not in the program."""
    prof.disable()
    prof.clear()
    assert not prof.session_active() and not prof.recording()
    with jax.profiler.trace(str(tmp_path)):
        assert prof.session_active() and prof.recording()
        with prof.span("traced"):
            pass
    assert not prof.session_active() and not prof.recording()
    with prof.span("untraced"):
        pass
    assert [r.name for r in prof.spans()] == ["traced"]
    with prof.trace(str(tmp_path / "own")):     # the module's own doorway
        assert prof.recording()
    prof.clear()


def test_named_sets_the_module_name():
    f = prof.named(lambda x: x + 1, "lloyd_step")
    assert "module @jit_lloyd_step" in jax.jit(f).lower(1.0).as_text()


# ---------------------------------------------------------------------- #
# names inside the programs                                              #
# ---------------------------------------------------------------------- #
def test_train_step_carries_every_lm_scope_and_its_module_name():
    import optax

    model, params = small_lm((2, 1, 1, 1), compute_dtype=jnp.bfloat16)
    assert model.packed_step_supported
    tx = optax.adam(1e-3)
    step = model.make_train_step(tx)
    toks = model.shard_batch(np.zeros((2, 16), np.int32))
    module, parts = scopes_of(step.lower(params, tx.init(params), toks))
    assert module == "jit_train_step"
    assert set(LM_SCOPES) <= parts, set(LM_SCOPES) - parts


def test_unpacked_train_step_and_the_other_lm_programs_are_named():
    import optax
    from heat_tpu.core import fusion

    model, params = small_lm()
    tx = optax.adam(1e-3)
    toks = model.shard_batch(np.zeros((2, 16), np.int32))
    with fusion.step_override(False):           # the check_vma branch
        step = model.make_train_step(tx)
        module, parts = scopes_of(step.lower(params, tx.init(params), toks))
        assert module == "jit_train_step"
        assert {"attn.core", "mlp", "loss", "optimizer"} <= parts
        lg = model.loss_and_grad_fn()
        assert scopes_of(lg.lower(params, toks))[0] == "jit_loss_and_grad"
    assert scopes_of(model.loss_and_grad_fn().lower(params, toks))[0] \
        == "jit_loss_and_grad"
    assert scopes_of(model.logits_fn().lower(params, toks))[0] == "jit_logits"
    model.generate(params, np.ones((1, 4), np.int32), 2)
    gen = next(v for k, v in model._step_cache.items()
               if k[0] == "generate")
    module, parts = scopes_of(gen.lower(
        params, jnp.zeros((1, 8), jnp.int32), jnp.int32(4),
        jax.random.key(0)))
    assert module == "jit_generate"
    assert {"attn.core", "cache.write", "sample", "embed"} <= parts
    # generate() runs the engine's step body over the model's own cache, a
    # leaf a layer: nothing is copied out for a `cache.read` to name
    assert "cache.read" not in parts


def test_moe_block_carries_its_scope():
    grid = ht.MeshGrid((1, 1, 1, 1), AXES, devices=jax.devices()[:1])
    model = TransformerLM(grid, TransformerLMConfig(
        vocab=64, d_model=32, n_heads=4, n_layers=2, moe_experts=2))
    params = model.init(0)
    toks = model.shard_batch(np.zeros((2, 16), np.int32))
    _m, parts = scopes_of(model.loss_and_grad_fn().lower(params, toks))
    assert "moe" in parts and "mlp" not in parts


@pytest.mark.parametrize("shape", [(1, 1, 1, 1), (2, 1, 2, 1)], ids=str)
def test_decode_programs_carry_their_names_and_scopes(shape):
    """The dense engine's two programs, as plain ``jit``s (one device) and
    as ``shard_map``s (dp 2 x tp 2): the same names and scopes."""
    from heat_tpu.serve.decode import DecodeConfig, DecodeEngine

    model, params = small_lm(shape)
    n = 2 * model.dp_world
    with DecodeEngine(model, params,
                      DecodeConfig(slots=n, max_seq_len=32)) as eng:
        step = eng._step_prog()
        module, parts = scopes_of(step.lower(
            params, *eng._cache, eng._pos, jnp.zeros(n, bool),
            eng._toks, jax.random.key(0)))
        assert module == "jit_decode_step"
        assert {"attn.core", "cache.write", "attn.qkv", "mlp", "head",
                "sample", "embed"} <= parts
        # a layer's lanes are leaves of their own: the row scatter is the
        # only write and the attention reads the leaf where it lies, so
        # nothing is copied out for a `cache.read` to name
        assert "cache.read" not in parts
        prefill = eng._prefill_prog(8)
        module, parts = scopes_of(prefill.lower(
            params, *eng._cache, eng._pos, eng._toks,
            jnp.zeros(8, jnp.int32), jnp.int32(3), jnp.int32(0),
            jax.random.key(0)))
        assert module == "jit_decode_prefill"
        assert {"attn.core", "cache.write", "sample"} <= parts
        assert "cache.read" not in parts


def test_pattern_decode_programs_nest_their_new_scopes_under_known_ones():
    """A per-layer pattern's programs keep the two program names, and every
    new scope sits INSIDE one the trace reduction knows (`attn.qkv/ssm.in`,
    `attn.core/ssm.scan`, `cache.write/ring`, ...), so a reader that knows
    only the outer name still charges the time to the right layer."""
    from heat_tpu.nn.transformer import sambay_pattern
    from heat_tpu.serve.decode import DecodeConfig, DecodeEngine

    grid = ht.MeshGrid((1, 1, 1, 1), AXES, devices=jax.devices()[:1])
    model = TransformerLM(grid, TransformerLMConfig(
        vocab=64, d_model=32, n_heads=4, n_kv_heads=2, n_layers=8, d_ff=64,
        rope=False, pattern=sambay_pattern(8), window=8, d_inner=64,
        d_state=4, dt_rank=2))
    params = model.init(0)

    def nested(lowered):
        txt = lowered.as_text(debug_info=True)
        return {m for m in re.findall(
            r"((?:attn\.qkv|attn\.core|cache\.read|cache\.write)/[\w.]+)",
            txt)}

    with DecodeEngine(model, params,
                      DecodeConfig(slots=2, max_seq_len=32)) as eng:
        step = eng._step_prog().lower(
            params, *eng._cache, eng._pos, jnp.zeros(2, bool), eng._toks,
            jax.random.key(0))
        module, parts = scopes_of(step)
        assert module == "jit_decode_step"
        assert {"attn.qkv", "attn.core", "attn.proj", "mlp", "head", "sample",
                "embed", "cache.write"} <= parts
        # rings, the lane and the states are read IN PLACE by the layer's
        # own operations and a state is its update's (donated) output:
        # nothing is copied, so `cache.read` names no operation
        assert "cache.read" not in parts
        assert {"attn.qkv/ssm.in", "attn.core/ssm.step",
                "attn.core/attn.window", "attn.core/attn.full",
                "attn.core/attn.cross", "attn.core/gmu", "cache.write/ring",
                "cache.write/lane"} <= nested(step)
        prefill = eng._prefill_prog(16).lower(
            params, *eng._cache, eng._pos, eng._toks,
            jnp.zeros(16, jnp.int32), jnp.int32(3), jnp.int32(0),
            jax.random.key(0))
        module, parts = scopes_of(prefill)
        assert module == "jit_decode_prefill"
        assert {"attn.qkv/ssm.in", "attn.core/ssm.scan",
                "attn.core/attn.window", "attn.core/attn.full",
                "attn.core/attn.cross", "attn.core/gmu"} <= nested(prefill)
        assert {"cache.write", "sample", "head"} <= parts


def test_lloyd_programs_carry_their_names_and_scopes():
    from heat_tpu.cluster import kmeans as km

    x = ht.array(np.zeros((64, 8), np.float32), split=0)
    xp, comm = x.larray, x.comm
    c = jnp.zeros((3, 8), jnp.float32)
    lloyd = {"lloyd.norms", "lloyd.dist", "lloyd.argmin", "lloyd.sums",
             "lloyd.inertia", "lloyd.update"}
    fused = km._lloyd_fused_fn(xp.shape, jnp.dtype(jnp.float32), 3, 64, comm,
                               None, None, None)
    module, parts = scopes_of(fused.lower(xp, c))
    assert module == "jit_lloyd_step"
    assert lloyd | {"lloyd.psum"} <= parts, (lloyd | {"lloyd.psum"}) - parts
    legacy = km._lloyd_step_fn(xp.shape, jnp.dtype(jnp.float32), 3, 64, comm)
    module, parts = scopes_of(legacy.lower(xp, c))
    assert module == "jit_lloyd_step" and lloyd <= parts
    assign = km._assign_fn(xp.shape, jnp.dtype(jnp.float32), 3, 64, comm)
    module, parts = scopes_of(assign.lower(xp, c))
    assert module == "jit_lloyd_assign"
    assert {"lloyd.norms", "lloyd.dist", "lloyd.argmin",
            "lloyd.inertia"} <= parts
    fori = km._lloyd_fori_fn(xp.shape, jnp.dtype(jnp.float32), 3, 64, comm)
    assert scopes_of(fori.lower(xp, c, 2))[0] == "jit_lloyd_fori"
    stream = km._stream_partial_fn(xp.shape, jnp.dtype(jnp.float32), 3, comm,
                                   0, None, None, None)
    module, parts = scopes_of(stream.lower(
        xp, c, jnp.int32(64), jnp.zeros((3, 8)), jnp.zeros(3), jnp.zeros(())))
    assert module == "jit_lloyd_stream" and "lloyd.psum" in parts


def test_fusion_programs_are_named_by_family():
    from heat_tpu.core import fusion

    fusion.capture_hlo(True)
    try:
        a = ht.arange(16, dtype=ht.float32, split=0)
        float(((a * 2.0 + 1.0) * a - 3.0).sum().item())
        assert "jit_flush" in fusion.last_hlo()
    finally:
        fusion.capture_hlo(False)

    def fn(p, x):
        return p - 0.1 * x

    step = fusion.trace_step(fn)
    step(ht.ones(8, split=0), ht.ones(8, split=0))
    rec = next(v for k, v in fusion.program_cache()._programs.items()
               if k[0] == "step" and k[1] is fn)
    assert rec.jitted.__name__ == "traced_step"


def _kernel_names(jaxpr, out):
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            out.append(eqn.params["name"])
        for v in eqn.params.values():
            inner = getattr(v, "jaxpr", v)
            if hasattr(inner, "eqns"):
                _kernel_names(inner, out)
    return out


@pytest.fixture
def pallas_on():
    pk.set_pallas(True)         # the backward kernels, not the dense fallback
    yield
    pk.set_pallas(None)


def test_every_pallas_call_has_its_name(pallas_on):
    q = jnp.ones((1, 2, 128, 64), jnp.float32)

    def loss(q, k, v):
        return pk.flash_attention(q, k, v, causal=True).sum()

    assert _kernel_names(jax.make_jaxpr(loss)(q, q, q).jaxpr, []) \
        == ["flash_fwd"]
    got = _kernel_names(jax.make_jaxpr(
        jax.grad(loss, argnums=(0, 1, 2)))(q, q, q).jaxpr, [])
    assert sorted(got) == ["flash_bwd_dkv", "flash_bwd_dq", "flash_fwd"]
    x = jnp.ones((256, 18), jnp.float32)
    assert _kernel_names(jax.make_jaxpr(pk.cdist_tile)(x, x).jaxpr, []) \
        == ["cdist_tile"]
    xs, cs = jnp.ones((256, 64), jnp.float32), jnp.ones((8, 64), jnp.float32)
    mask = jnp.ones((256, 1), jnp.float32)
    assert _kernel_names(jax.make_jaxpr(
        lambda a, b, m: pk.kmeans_step_tile(a, b, m, block_rows=128,
                                            sums_mode="loop"))(
        xs, cs, mask).jaxpr, []) == ["kmeans_step_tile"]
    # and no pallas_call in the file is left without one
    src = open(pk.__file__).read()
    assert src.count("pl.pallas_call(") == src.count("        name=\"") == 5


# ---------------------------------------------------------------------- #
# the spans the engines leave behind                                     #
# ---------------------------------------------------------------------- #
def test_train_step_spans(recording):
    import optax

    model, params = small_lm()
    tx = optax.adam(1e-3)
    step = model.make_train_step(tx)
    opt = tx.init(params)
    toks = model.shard_batch(np.zeros((2, 16), np.int32))
    for _ in range(3):
        params, opt, _loss = step(params, opt, toks)
    recs = prof.spans()
    steps = [r for r in recs if r.name == "train_step"]
    assert len(steps) == 3
    for s in steps:
        kids = sorted(r.name for r in recs if r.parent_id == s.id)
        assert kids == ["train_step.dispatch", "train_step.place"]


def test_decode_engine_gives_every_request_its_spans(recording):
    from heat_tpu.serve.decode import DecodeConfig, DecodeEngine

    model, params = small_lm()
    lens = [(4, 3), (5, 6), (7, 4), (3, 1)]     # more requests than slots
    with DecodeEngine(model, params,
                      DecodeConfig(slots=2, max_seq_len=32)) as eng:
        futs = [eng.submit(np.arange(1, 1 + p), n) for p, n in lens]
        outs = [f.result(120) for f in futs]
    recs = prof.spans()
    reqs = [r for r in recs if r.name == "decode.request"]
    assert len(reqs) == len(lens)
    by_rid = {r.attrs["rid"]: r for r in reqs}
    assert len(by_rid) == len(lens)
    for (p, n), out, rid in zip(lens, outs, sorted(by_rid)):
        r = by_rid[rid]
        kids = {k.name: k for k in recs if k.parent_id == r.id}
        assert set(kids) == {"decode.queue", "decode.prefill"}
        assert all(k.attrs["rid"] == rid for k in kids.values())
        assert r.t0 <= kids["decode.queue"].t0 <= kids["decode.queue"].t1 \
            <= kids["decode.prefill"].t1 <= r.t1
        assert r.attrs["prompt"] == p and r.attrs["bucket"] == 8
        n_tokens = len(out) - p
        assert r.attrs["n_out"] == n_tokens == n
        assert [e for e, _t in r.events] == ["token"] * n_tokens
        # the first token closes the prefill
        assert abs(r.events[0][1] - kids["decode.prefill"].t1) < 0.05
    names = Counter(r.name for r in recs)
    assert names["decode.prefill.dispatch"] == len(lens)
    assert names["decode.emit"] == len(lens)
    assert names["decode.step"] == names["decode.step.dispatch"] >= 5
    assert names["decode.fetch"] == names["decode.step"] + len(lens)
    assert names["decode.loop"] >= 1 and names["decode.grant"] >= 1
    step = next(r for r in recs if r.name == "decode.step")
    assert 1 <= step.attrs["n_live"] <= 2
    worker = {r.thread for r in recs if r.name == "decode.step"}
    assert worker == {reqs[0].thread} and threading.get_ident() not in worker


def test_kmeans_fit_gives_one_iter_and_one_sync_per_iteration(recording):
    data = np.random.default_rng(0).normal(size=(200, 8)).astype(np.float32)
    km = ht.cluster.KMeans(n_clusters=3, max_iter=6, tol=-1.0,
                           random_state=0).fit(ht.array(data, split=0))
    recs = prof.spans()
    names = Counter(r.name for r in recs)
    assert km.n_iter_ == 6
    assert names["kmeans.fit"] == names["kmeans.assign"] == 1
    assert names["kmeans.iter"] == names["kmeans.sync"] == 6
    assert names["fit_step"] == names["fit_step.lookup"] \
        == names["fit_step.dispatch"] == 6
    assert names["fit_step.fallback"] == 0
    fit = next(r for r in recs if r.name == "kmeans.fit")
    iters = [r for r in recs if r.name == "kmeans.iter"]
    assert [r.attrs["it"] for r in iters] == [1, 2, 3, 4, 5, 6]
    assert all(r.parent_id == fit.id for r in iters)
    for it in iters:
        kids = sorted(r.name for r in recs if r.parent_id == it.id)
        assert kids == ["fit_step", "kmeans.sync"]
    hits = [r.attrs["hit"] for r in recs if r.name == "fit_step"]
    assert hits[1:] == [True] * 5               # one program, looked up


def test_flush_and_traced_step_spans(recording):
    from heat_tpu.core import fusion

    a = ht.arange(32, dtype=ht.float32, split=0)
    for _ in range(2):
        ((a * 2.0 + 1.0) * a - 3.0 + a * a).sum().item()
    recs = prof.spans()
    flushes = [r for r in recs if r.name == "flush" and "hit" in r.attrs]
    assert flushes and all(r.attrs["n_nodes"] >= 1 for r in flushes)
    assert flushes[-1].attrs["hit"] is True
    kids = {r.name for r in recs if r.parent_id == flushes[-1].id}
    assert kids == {"flush.dispatch"}            # a hit builds nothing
    prof.clear()

    def fn(p, x):
        return p - 0.25 * x

    step = fusion.trace_step(fn)
    p = ht.ones(8, split=0)
    for _ in range(3):
        p = step(p, ht.ones(8, split=0))
    ts = [r for r in prof.spans() if r.name == "traced_step"]
    assert [r.attrs["hit"] for r in ts] == [False, True, True]
    inner = [r.name for r in prof.spans()
             if r.name.startswith("traced_step.")]
    assert inner == ["traced_step.prime", "traced_step.dispatch",
                     "traced_step.dispatch"]

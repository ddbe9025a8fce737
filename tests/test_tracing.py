"""The program's spans (``repro.tracing``): off and free without a profiler
session, nested and bounded with one, and placed at the served path's
layer boundaries with row counts that match what the pooled steps were
given; and the names under which the pooled programs compile, on which
the benchmark's device readers depend."""
import re

import jax
import numpy as np
import pytest

from bench import check, deploy
from repro import tracing
from repro.core import shortest_path_route
from tests.bench.tiny import tiny


@pytest.fixture
def profiler(tmp_path):
    """A profiler session (spans on) for the body of the ``with``."""
    class Session:
        def __enter__(self):
            tracing.clear()
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(str(tmp_path), profiler_options=opts)

        def __exit__(self, *exc):
            jax.profiler.stop_trace()

    return Session()


def test_off_records_nothing_and_set_is_a_no_op():
    tracing.clear()
    with tracing.span("decode_round", sessions=3) as sp:
        assert not sp
        sp.set(W=8)
        with tracing.span("round.hop") as inner:
            assert inner is sp
    assert tracing.spans() == []


def test_on_spans_nest_with_parents_and_attrs(profiler):
    with profiler:
        with tracing.span("decode_round") as outer:
            assert outer
            with tracing.span("round.hop", server=1) as hop:
                hop.set(rows=4)
            with tracing.span("round.sync"):
                pass
            outer.set(sessions=2)
        with tracing.span("retire", sid=7):
            pass
    with tracing.span("after") as sp:
        assert not sp
    got = {s.name: s for s in tracing.spans()}
    assert set(got) == {"decode_round", "round.hop", "round.sync", "retire"}
    d = got["decode_round"]
    assert d.parent is None and d.attrs == {"sessions": 2}
    assert got["round.hop"].parent == d.id
    assert got["round.hop"].attrs == {"server": 1, "rows": 4}
    assert got["round.sync"].parent == d.id
    assert got["retire"].parent is None and got["retire"].attrs == {"sid": 7}
    for s in got.values():
        assert s.start <= s.end
    assert d.start <= got["round.hop"].start <= got["round.hop"].end \
        <= got["round.sync"].start <= got["round.sync"].end <= d.end
    tracing.clear()
    assert tracing.spans() == []


def test_buffer_keeps_the_last_max_spans(profiler):
    extra = 10
    with profiler:
        for i in range(tracing.MAX_SPANS + extra):
            with tracing.span("s", i=i):
                pass
    got = tracing.spans()
    assert len(got) == tracing.MAX_SPANS
    assert got[0].attrs["i"] == extra and got[-1].attrs["i"] == \
        tracing.MAX_SPANS + extra - 1
    tracing.clear()


def _tiny_system():
    _, cfg, mix = tiny()
    params = check.reference_module(cfg).make_params(cfg["model"], 5)
    return deploy.build(cfg, mix, params)


def _spy(srv, name, seen):
    """Record each call of ``srv.<name>`` with the pool rows it saw."""
    fn = getattr(srv, name)

    def spy(*args, **kw):
        seen.append((srv, args, dict(srv.pool.rows)))
        return fn(*args, **kw)

    setattr(srv, name, spy)


def _children(spans, parent):
    return [s for s in sorted(spans, key=lambda s: s.start)
            if s.parent == parent.id]


def test_engine_spans_and_row_counts(profiler):
    system, ctl = _tiny_system()
    rng = np.random.default_rng(0)
    lens = [20, 30, 9]  # buckets 32, 32 and 16: two groups
    prefill_calls, hop_calls = [], []
    for srv in system.servers.values():
        _spy(srv, "prefill_row", prefill_calls)
        _spy(srv, "round_rows", hop_calls)
    with profiler:
        route, *_ = ctl.admit(0, 0.0)
        sids = [system.create_session(rng.integers(2, 512, size=n), 0,
                                      route, 4) for n in lens]
        assert system.try_admit_sessions(sids) == sids
        system.prefill_round()
        system.decode_round()
        system.retire_session(sids[0])
    spans = tracing.spans()
    tracing.clear()
    by = {}
    for s in spans:
        by.setdefault(s.name, []).append(s)
    assert by["route"][0].attrs == {"client": 0}
    assert by["admit"][0].attrs == {"offered": 3, "granted": 3,
                                    "sids": sids}
    assert by["retire"][0].attrs == {"sid": sids[0]}

    # prefill: one hop span per (group, hop), inside it one one-row
    # dispatch per member and nothing staged on the host
    (pr,) = by["prefill_round"]
    assert pr.attrs == {"groups": 2}
    hops = [s for s in _children(spans, pr) if s.name == "prefill.hop"]
    assert len(hops) == 2 * len(route.servers)
    span_of = {sid: n for sid, n in zip(sids, lens)}
    members = {32: sids[:2], 16: sids[2:]}
    calls = iter(prefill_calls)
    for hop in hops:
        assert _children(spans, hop) == []
        group = members[hop.attrs["t_pad"]]
        assert hop.attrs["rows"] == len(group)
        useful = 0
        for sid in group:
            srv, args, rows = next(calls)
            h, layer_active = np.asarray(args[0]), np.asarray(args[2])
            assert args[1] == rows[sid]
            assert hop.attrs["server"] == srv.sid
            assert h.shape[:2] == (1, hop.attrs["t_pad"])
            assert layer_active.shape == (hop.attrs["m"], 1)
            assert hop.attrs["m"] == srv.m
            useful += int(layer_active.sum()) * span_of[sid]
        assert hop.attrs["tok_layers_useful"] == useful
        assert hop.attrs["tok_layers_computed"] == \
            len(group) * hop.attrs["t_pad"] * hop.attrs["m"]
    assert next(calls, None) is None
    assert sum(h.attrs["tok_layers_useful"] for h in hops) == \
        sum(lens) * system.cfg.n_layers
    fin = [s for s in _children(spans, pr) if s.name == "prefill.finalize"]
    assert sorted(s.attrs["sid"] for s in fin) == sorted(sids)

    # decode: prepare, one hop span per (hop, server), tail, sync
    (dr,) = by["decode_round"]
    assert dr.attrs == {"sessions": 3, "W": system._round_width}
    kids = _children(spans, dr)
    n_hops = len(route.servers)
    assert [c.name for c in kids] == (["round.prepare"]
                                      + ["round.hop"] * n_hops
                                      + ["round.tail", "round.sync"])
    assert len(hop_calls) == n_hops
    for hop, (srv, args, _) in zip(kids[1:1 + n_hops], hop_calls):
        mask = np.asarray(args[5])  # layer_active
        assert hop.attrs["server"] == srv.sid
        assert hop.attrs["row_layers_useful"] == int(mask.sum())
        assert hop.attrs["row_layers_computed"] == mask.size == \
            hop.attrs["rows"] * hop.attrs["m"]


def _module_name(fn, args, kw):
    """The name of the module ``fn`` lowers to for these arguments."""
    abstract = jax.tree.map(
        lambda a: (jax.ShapeDtypeStruct(a.shape, a.dtype)
                   if isinstance(a, jax.Array) else a), (args, kw))
    text = fn.lower(*abstract[0], **abstract[1]).as_text()
    return re.search(r"module @(\w+)", text).group(1)


def test_pooled_programs_compile_under_stable_names():
    system, _ = _tiny_system()
    srv = next(iter(system.servers.values()))
    seen = {}
    for obj, attr in ((srv, "_round_step"), (srv, "_prefill_row"),
                      (srv, "_step"), (system, "_round_tail")):
        fn, calls = getattr(obj, attr), []
        seen[attr] = (fn, calls)

        def spy(*args, _fn=fn, _calls=calls, **kw):
            _calls.append(_module_name(_fn, args, kw))
            return _fn(*args, **kw)

        setattr(obj, attr, spy)
    route, _ = shortest_path_route(system.problem, system.alive_placement(),
                                   0)
    sid = system.create_session(np.arange(2, 14), 0, route, 3)
    assert system.try_admit_sessions([sid]) == [sid]
    system.prefill_round()
    system.decode_round()  # fused: round step and tail
    system.decode_mode = "serial"
    system.decode_round()  # host-staged: the pooled decode step
    names = {attr: set(calls) for attr, (_, calls) in seen.items()}
    assert names == {"_round_step": {"jit_hop"},
                     "_prefill_row": {"jit_step"},
                     "_step": {"jit_decode_step"},
                     "_round_tail": {"jit_tail"}}

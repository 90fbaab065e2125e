"""Per-row split-K decode under tensor parallelism: spy + exactness.

The continuous-batching scheduler drives decode with a (B,) vector of
per-row cache cursors.  Before the vector-offset generalization,
``_attn_core`` guarded split-K behind a scalar offset, so exactly the
serving configuration that NEEDS the fast path (ragged cursors under TP)
silently regressed to plain attention -- the paper's anti-pattern of a
fast path that is fast only for the shapes nobody serves.  These tests
pin the fix on the ``host_mesh8`` fixture (8 simulated devices,
tests/conftest.py) across the three cache-sharding modes of
``layers.attention``:

  seq-model  -- tp > 1 and n_kv_heads_eff % tp != 0: the cache sequence
                axis is sharded over ("model",) (few-KV-head GQA);
  seq-all    -- tp > 1 and B does not divide the batch axes: sharded over
                every mesh axis (long-context / ragged-batch);
  kv-shard   -- KV heads divide tp: no sequence sharding, plain masked
                attention IS the right path (the spy asserts split-K is
                NOT taken -- no gratuitous collectives).

A module-level spy wraps ``layers._attn_decode_splitk`` /
``layers._attn_plain``; it fires at trace time, so counts are per
compiled signature, not per step.  Exactness: every scheduler stream
must equal the solo ``ServeEngine.generate`` of that request BITWISE --
in the split-K modes the solo decode takes the same seq_axes/chunking as
the batched per-row decode, so the pmax/psum softmax reconciliation is
identical per row.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.chatglm3_6b import SMOKE
from repro.models import api as A
from repro.models import layers as L
from repro.serve.engine import ServeEngine
from repro.serve.scheduler import ContinuousBatchingScheduler, Request

MAX_LEN = 40          # divisible by the 8-chunk (4,2) split and the 2-chunk tp split

#: n_kv_heads=1 forces n_kv_heads_eff % tp != 0 under tp=2 -> seq-model mode
KV1 = dataclasses.replace(SMOKE, name="chatglm3-smoke-kv1", n_kv_heads=1)

#: mode -> (mesh shape, config, scheduler slots).  seq-all uses slots=3
#: (3 does not divide the data axis of 4) so BOTH the B=3 pool and the
#: B=1 solo runs shard the cache over every axis -- same 8-way chunking,
#: hence bitwise-comparable.  The (1,2) meshes make bat_prod=1, so solo
#: and batched likewise agree on seq_axes.
MODES = {
    "seq-model": ((1, 2), KV1, 4),
    "seq-all": ((4, 2), SMOKE, 3),
    "kv-shard": ((1, 2), SMOKE, 4),
}

_params_cache: dict = {}


class _Spy:
    """Trace-time call counters for the two decode attention kernels."""

    def __init__(self):
        self.splitk = 0
        self.plain = 0

    def install(self, monkeypatch):
        real_sk, real_pl = L._attn_decode_splitk, L._attn_plain

        def sk(*a, **k):
            self.splitk += 1
            return real_sk(*a, **k)

        def pl(*a, **k):
            self.plain += 1
            return real_pl(*a, **k)

        monkeypatch.setattr(L, "_attn_decode_splitk", sk)
        monkeypatch.setattr(L, "_attn_plain", pl)
        return self


def _engine(cfg) -> tuple:
    key = cfg.name
    if key not in _params_cache:
        api = A.build(cfg)
        _params_cache[key] = (api, api.init(jax.random.PRNGKey(0)))
    api, params = _params_cache[key]
    return api, params


def _ragged_requests(cfg, n, *, prompt_len=8, seed=3):
    """Staggered arrivals so slots sit at ragged cursor positions."""
    rng = np.random.RandomState(seed)
    return [
        Request(rid=i, prompt=rng.randint(0, cfg.vocab, size=prompt_len),
                max_new_tokens=int(rng.randint(3, 8)), seed=i, arrival=i)
        for i in range(n)
    ]


def _solo_streams(eng, reqs):
    return {
        r.rid: [int(t) for t in np.asarray(
            eng.generate(jnp.asarray(r.prompt)[None],
                         max_new_tokens=r.max_new_tokens, seed=r.seed))[0]]
        for r in reqs
    }


def _run_mode(mode, monkeypatch, *, prefill_chunk=None, prompt_len=8):
    mesh_shape, cfg, slots = MODES[mode]
    mesh = jax.make_mesh(mesh_shape, ("data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)
    api, params = _engine(cfg)
    spy = _Spy().install(monkeypatch)
    with jax.set_mesh(mesh):
        eng = ServeEngine(api, params, max_len=MAX_LEN)
        reqs = _ragged_requests(cfg, slots + 2, prompt_len=prompt_len)
        sched = ContinuousBatchingScheduler(eng, slots=slots,
                                            prefill_chunk=prefill_chunk)
        done = sched.run([dataclasses.replace(r) for r in reqs])
        decode_spy = (spy.splitk, spy.plain)
        solo = _solo_streams(eng, reqs)
    return done, solo, decode_spy


@pytest.mark.parametrize("mode", ["seq-model", "seq-all"])
def test_splitk_taken_with_ragged_cursors_under_tp(host_mesh8, mode,
                                                   monkeypatch):
    """The pool decode with (B,) cursors traces the SPLIT-K kernel, never
    the plain fallback, and every stream is bitwise the solo stream."""
    done, solo, (n_splitk, n_plain) = _run_mode(mode, monkeypatch)
    assert n_splitk >= 1, "per-row decode did not take the split-K path"
    assert n_plain == 0, (
        f"per-row decode regressed to plain attention ({n_plain} traces)")
    for rid, toks in solo.items():
        assert done[rid].tokens == toks, f"rid {rid} diverged from solo"


def test_kv_sharded_mode_stays_plain(host_mesh8, monkeypatch):
    """When KV heads divide tp there is no sequence sharding: plain masked
    attention is correct and split-K's collectives would be pure waste."""
    done, solo, (n_splitk, n_plain) = _run_mode("kv-shard", monkeypatch)
    assert n_splitk == 0, "split-K traced despite a KV-head-sharded cache"
    assert n_plain >= 1
    for rid, toks in solo.items():
        assert done[rid].tokens == toks


@pytest.mark.parametrize("mode", ["seq-model", "seq-all"])
def test_chunked_prefill_scheduler_bitwise_under_tp(host_mesh8, mode,
                                                    monkeypatch):
    """Chunked admission (prefill_chunk=8 on 16-token prompts, q_chunk
    aligned) composed with per-row split-K decode stays bitwise equal to
    solo generate -- I1 and I5 hold together under the mesh."""
    done, solo, (n_splitk, n_plain) = _run_mode(
        mode, monkeypatch, prefill_chunk=8, prompt_len=16)
    assert n_splitk >= 1 and n_plain == 0
    for rid, toks in solo.items():
        assert done[rid].tokens == toks, f"rid {rid} diverged from solo"

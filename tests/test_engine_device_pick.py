"""The decode round picks greedy tokens on the device and copies back only
the (B,) ids (``models/api.decode_pick_fn``, ``serve/engine._DeviceRow``).

Against a host loop in the engine's place — the plain ``api.decode_fn``,
every row's logits copied to the host, picked with ``np.argmax`` or the
seeded rng — on a tiny qwen2 (contiguous and paged KV) and a tiny LFM2
pattern stack (paged; a pattern stack serves through pages only), with
slots left empty in the batch: greedy streams are bit-identical, and a
batch mixing greedy and temperature rows draws the same tokens for a
fixed seed. ``serve.host_logit_rows`` counts the rows whose logits reached
the host: none for greedy rows, each temperature row, and every row of a
round a ``corrupt`` fault poisons.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.faults import FaultPlan, FaultSpec
from repro.models import api
from repro.obs import trace as obs_trace
from repro.serve import Engine, Request, ServeConfig
from repro.serve.engine import _DeviceRow

VOCAB = 64


class HostLoopEngine(Engine):
    """The engine with the round's picks made on the host: the decode call
    is the bare ``api.decode_fn`` (its ids are -1, so a pick that used
    them would show), and each row is copied before ``_pick`` reads it."""

    def __init__(self, cfg, params, scfg):
        super().__init__(cfg, params, scfg)
        step = jax.jit(api.decode_fn(cfg, precision=scfg.precision))
        B = scfg.max_batch

        def decode(p, tok, cache):
            logits, cache = step(p, tok, cache)
            return jnp.full((B,), -1, jnp.int32), logits, cache
        self.decode = decode

    def _pick(self, logits_row, req):
        return Engine._pick(self, np.asarray(logits_row), req)


def _qwen2():
    return dataclasses.replace(get_config("qwen2-0.5b"), n_layers=2,
                               d_model=32, n_heads=4, n_kv_heads=2, d_ff=64,
                               vocab=VOCAB)


def _lfm2():
    """Layers 0-6 of the published pattern at toy widths: 2 dense FFNs,
    then expert layers; conv, conv, attention, conv, conv, conv,
    attention."""
    cfg = get_config("lfm2-8b-a1b")
    return dataclasses.replace(
        cfg, n_layers=7, d_model=64, n_heads=4, n_kv_heads=2, d_ff=96,
        vocab=VOCAB, param_dtype="float32",
        moe=dataclasses.replace(cfg.moe, num_experts=8, top_k=2, d_ff=32))


MODELS = {"qwen2": _qwen2, "lfm2": _lfm2}
LAYOUTS = {"contiguous": dict(max_batch=4, max_len=32),
           "paged": dict(max_batch=4, max_len=32, kv_layout="paged",
                         kv_block_size=8, prefix_cache=False)}
CASES = [("qwen2", "contiguous"), ("qwen2", "paged"), ("lfm2", "paged")]


@pytest.fixture(scope="module")
def models():
    """``name -> (cfg, params)``, each built once per module."""
    built = {}

    def get(name):
        if name not in built:
            cfg = MODELS[name]()
            built[name] = cfg, api.init_params(cfg, jax.random.PRNGKey(1))
        return built[name]
    return get


def _requests(temps):
    """One request per entry of ``temps`` (None: greedy), prompts of
    different lengths, outputs of different lengths so slots empty out at
    different rounds."""
    rng = np.random.default_rng(11)
    return [Request(uid=i, prompt=rng.integers(0, VOCAB, 3 + 2 * i).astype(
        np.int32), max_new_tokens=4 + 3 * i, temperature=t)
        for i, t in enumerate(temps)]


def _drain(models, engine_cls, name, layout, temps, seed=0):
    cfg, params = models(name)
    eng = engine_cls(cfg, params, ServeConfig(seed=seed, **LAYOUTS[layout]))
    reqs = _requests(temps)
    for r in reqs:
        eng.submit(r)
    done = eng.run_until_drained()
    assert all(r.status == "ok" for r in done)
    return eng, {r.uid: list(r.out_tokens) for r in done}


def _host_rows(eng):
    return eng.metrics.counter("serve.host_logit_rows").value


@pytest.mark.parametrize("name,layout", CASES)
def test_greedy_streams_match_host_argmax(models, name, layout):
    """(a) Three requests in four slots, retiring at different rounds: the
    device's picks give the host loop's streams bit for bit."""
    temps = [None] * 3
    _, want = _drain(models, HostLoopEngine, name, layout, temps)
    eng, got = _drain(models, Engine, name, layout, temps)
    assert got == want
    assert eng.stats["occupancy"] < 1.0          # empty slots were decoded
    assert _host_rows(eng) == 0                  # (c) all greedy


@pytest.mark.parametrize("name,layout", CASES[:2])
def test_mixed_temperature_rows_draw_as_the_host_loop(models, name,
                                                     layout):
    """(b, c) Greedy and temperature rows in one batch: for a fixed seed the
    same streams as the host loop (the rng draws in the same row order),
    and only the temperature rows' logits come to the host, one row per
    decode-round pick."""
    temps = [None, 1.5, None, 0.7]
    for seed in (0, 3):
        _, want = _drain(models, HostLoopEngine, name, layout, temps,
                         seed=seed)
        eng, got = _drain(models, Engine, name, layout, temps, seed=seed)
        assert got == want
        sampled = sum(len(got[i]) - 1 for i, t in enumerate(temps)
                      if t is not None)
        assert sampled > 0 and _host_rows(eng) == sampled


def test_corrupt_round_copies_every_row(models):
    """(d) The round a ``corrupt`` directive hits takes the host path: the
    whole logits copied, poisoned, every active row picked from them
    (``host_rows == active`` on its ``engine.sample`` span, and those
    requests recorded as poisoned); every other round copies none."""
    cfg, params = models("qwen2")
    eng = Engine(cfg, params, ServeConfig(**LAYOUTS["contiguous"]))
    for r in _requests([None] * 3):
        eng.submit(r)
    obs_trace.TRACER.clear()
    obs_trace.enable()
    try:
        with FaultPlan([FaultSpec(site="engine.decode_round",
                                  kind="corrupt", nth=2)]):
            done = eng.run_until_drained()
        ev = [e for e in obs_trace.TRACER.events()
              if e["name"] == "engine.sample"]
    finally:
        obs_trace.disable()
        obs_trace.TRACER.clear()
    assert all(r.status == "ok" for r in done)
    begins = [e["args"]["active"] for e in ev if e["ph"] == "B"]
    rows = [e["args"]["host_rows"] for e in ev if e["ph"] == "E"]
    assert len(begins) == len(rows) == eng.stats["decode_steps"]
    assert rows[1] == begins[1] == 3
    assert rows[:1] + rows[2:] == [0] * (len(rows) - 1)
    assert _host_rows(eng) == 3
    assert eng.poisoned_uids == {0, 1, 2}


def test_decode_pick_returns_the_steps_logits_and_their_argmax(models):
    """The wrapper changes nothing the step computes: the same logits and
    cache as ``api.decode_fn``, and ids the first-index argmax of the last
    position, int32."""
    cfg, params = models("qwen2")
    cache = api.init_slot_cache(cfg, 3, 16)
    cache["len"] = jnp.asarray([0, 2, 5], jnp.int32)
    tok = jnp.asarray([[1], [7], [40]], jnp.int32)
    logits, want = jax.jit(api.decode_fn(cfg))(params, tok, cache)
    ids, logits2, got = jax.jit(api.decode_pick_fn(cfg))(params, tok, cache)
    np.testing.assert_array_equal(np.asarray(logits2), np.asarray(logits))
    jax.tree_util.tree_map(np.testing.assert_array_equal, got, want)
    assert ids.dtype == jnp.int32 and ids.shape == (3,)
    np.testing.assert_array_equal(
        np.asarray(ids), np.argmax(np.asarray(logits)[:, -1], axis=-1))


def test_device_row_moves_only_its_row_when_read():
    lg = jnp.asarray(np.arange(2 * 1 * 5, dtype=np.float32).reshape(2, 1, 5))
    row = _DeviceRow(lg, 1, 4)
    assert row.argmax() == 4 and row.shape == (5,) and not row.fetched
    host = np.asarray(row, np.float64)
    assert row.fetched and host.dtype == np.float64
    np.testing.assert_array_equal(host, [5, 6, 7, 8, 9])


def test_device_argmax_breaks_ties_at_the_first_index():
    x = np.zeros((3, 1, 8), np.float32)
    x[0, 0, [2, 5]] = 1.0                   # tie at 2 and 5
    x[1, 0, [6, 7]] = 3.0                   # tie at 6 and 7
    got = jax.jit(lambda a: jnp.argmax(a[:, -1], axis=-1))(jnp.asarray(x))
    np.testing.assert_array_equal(np.asarray(got), [2, 6, 0])
    np.testing.assert_array_equal(np.argmax(x[:, -1], axis=-1), [2, 6, 0])

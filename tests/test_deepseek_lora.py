"""DeepSeek-V2-Lite's blocks (MLA, YaRN, dropless DeepSeekMoE) and the LM
family's Bayesian LoRA path through ``Session.round()``, against the plain
reference of the chip benchmark (``benchmarks/chip/configs/
deepseek_v2_lite.py``, loaded by path), at a small size on seeded random
weights: d 64, 4 heads, c_kv 16, rope/nope 8/16, v 16, 8 experts top-2
plus 1 shared, 1 dense + 2 MoE layers, vocabulary 256, L 32, N 4."""
import copy
import dataclasses
import importlib.util
import json
import math
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.api import (DataSpec, ExperimentSpec, InferenceSpec, ObsSpec,
                       RunSpec, TopologySpec, build_session)
from repro.api.models import chunked_xent, init_trunk, lm_config, lora_init
from repro.configs import get_config
from repro.configs.registry import ARCHS
from repro.data.pipeline import make_lm_batch_sampler, zipf_cdf
from repro.models import moe as moe_lib
from repro.models.attention import mla_softmax_scale
from repro.models.modules import rope_scale, yarn_inv_freq
from repro.models.transformer import mla_hidden

ROOT = Path(__file__).resolve().parents[1]
CHIP = ROOT / "benchmarks" / "chip"
SEED = 2_147_483_711
ARCH = "deepseek-v2-lite-test"
N, U, L, RANK, ALPHA = 4, 2, 32, 4, 8.0


def _load(path, name):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


ref = _load(CHIP / "configs" / "deepseek_v2_lite.py", "deepseek_v2_lite_ref")

TINY = dataclasses.replace(
    get_config("deepseek-v2-lite"), name=ARCH, n_layers=3, d_model=64,
    n_heads=4, n_kv_heads=4, d_ff=96, vocab_size=256, n_experts=8, top_k=2,
    moe_d_ff=32, n_shared_experts=1, kv_lora_rank=16, qk_nope_head_dim=16,
    qk_rope_head_dim=8, v_head_dim=16)


def _cfg_doc() -> dict:
    """The chip configuration's file, cut to the test's size: published
    keys the reference reads, program sections the session reads."""
    doc = json.loads((CHIP / "configs" /
                      "deepseek_v2_lite.lora_ring8.json").read_text())
    doc = copy.deepcopy(doc)
    doc.update(hidden_size=64, num_attention_heads=4, kv_lora_rank=16,
               qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
               n_routed_experts=8, num_experts_per_tok=2,
               moe_intermediate_size=32, n_shared_experts=1,
               intermediate_size=96, vocab_size=256, n_layers=3, n_agents=N)
    doc["data"]["dataset_params"].update(vocab_size=256, seq_len=L)
    doc["inference"].update(lora_rank=RANK, lora_alpha=ALPHA)
    doc["topology"]["params"]["n"] = N
    return doc


DOC = _cfg_doc()
A = ref.arch(DOC)


@pytest.fixture(autouse=True)
def _tiny_arch(monkeypatch):
    monkeypatch.setitem(ARCHS, ARCH, TINY)


def _spec(topology, obs=False, seed=SEED):
    inf = {k: v for k, v in DOC["inference"].items()}
    data = DOC["data"]
    return ExperimentSpec(
        topology=topology,
        data=DataSpec(dataset="zipf_tokens",
                      dataset_params=dict(data["dataset_params"]),
                      batch_size=data["batch_size"],
                      local_updates=data["local_updates"]),
        inference=InferenceSpec(model="lm", arch=ARCH, **inf),
        run=RunSpec(n_rounds=1, seed=seed),
        obs=ObsSpec(enabled=obs, convergence=False))


SYNC = TopologySpec(kind="bidirectional_ring", params={"n": N})
# every directed edge fires in every window (P(no event) = e^-50), so each
# gossip window merges with the whole ring's weights: the synchronous round
GOSSIP = TopologySpec.gossip("bidirectional_ring", {"n": N},
                             {"kind": "poisson", "rate": 50.0,
                              "window_len": 1.0, "seed": 0})


def _leaf_norms(post, row0=None):
    out = {}
    for kind in ("mean", "rho"):
        arr = np.asarray(getattr(post, kind), np.float64)
        if row0 is not None:
            arr = arr - row0[kind][None, :]
        for s in post.layout.specs:
            name = ".".join(p.strip("'[]") for p in s.path.split("]["))
            out[f"{kind}.{name}"] = float(np.linalg.norm(
                arr[:, s.offset:s.offset + s.size]))
    return out


def _readings(session, rounds=2):
    post0 = session.posterior()
    row0 = {"mean": np.asarray(post0.mean[0], np.float64),
            "rho": np.asarray(post0.rho[0], np.float64)}
    losses = [session.round()["loss"]]
    grad = _leaf_norms(session.state.opt_state.mu)
    losses += [session.round()["loss"] for _ in range(rounds - 1)]
    return {"loss": losses, "grad": grad,
            "change": _leaf_norms(session.posterior(), row0)}


# The comparison that decides ``correct`` on the chip, with the cell's
# committed limits.  The program computes the trunk's matmuls in bfloat16
# (float32 accumulation), the reference in float32 at HIGHEST.  At this size
# (limits 0.1 / 0.08 / 0.25) the program reads loss 1.8e-4, grad 1.0e-2,
# change 2.0e-2; the reference with its trunk matmuls on float8 inputs (a
# precision step below bfloat16's 8 bits) reads 1.1e-3, 8.4e-2, 6.2e-2;
# the program with its state unchanged 2.2e-2, 1, 1; on half of each
# sequence 2.5e-2, 0.16, 0.17; with no exchange change 0.99.
correct = _load(CHIP / "chipbench" / "correct.py", "chipbench_correct")
LIMITS = json.loads((CHIP / "limits" /
                     "deepseek_v2_lite.lora_ring8.lm_sync.json").read_text())


def _verdict(prog, r):
    return correct.verdict(correct.train_numbers(prog, r), LIMITS)


@pytest.fixture(scope="module")
def reference_readings():
    return ref.train_readings(DOC, {"clock": None}, SEED, 2)


# ---------------------------------------------------------------------------
# blocks
# ---------------------------------------------------------------------------


def test_yarn_frequencies_and_mscale_by_hand():
    """DeepSeek-V2's published YaRN (factor 40 over 4,096 positions, beta
    32/1, rope dim 64, theta 1e4).  Correction range: 64 ln(4096 / (32 *
    2 pi)) / (2 ln 1e4) = 10.47 -> floor 10; 64 ln(4096 / (2 pi)) / (2 ln
    1e4) = 22.51 -> ceil 23.  Below pair 10 the plain frequency, above 23
    a 40th of it, between a linear ramp (pair 16: 6/13 of the way)."""
    cfg = get_config("deepseek-v2-lite")
    f = yarn_inv_freq(64, 1e4, cfg.rope_scaling)
    plain = 1e4 ** (-np.arange(32) / 32)
    np.testing.assert_allclose(f[:11], plain[:11], rtol=1e-12)
    np.testing.assert_allclose(f[23:], plain[23:] / 40, rtol=1e-12)
    ramp = 6 / 13
    np.testing.assert_allclose(f[16], plain[16] * (ramp / 40 + 1 - ramp),
                               rtol=1e-12)
    # mscale = 0.1 * 0.707 * ln 40 + 1 = 1.260804; the softmax scale is
    # 192^-1/2 * mscale^2 (1.589626 times the plain scale); cos/sin unscaled
    m = 0.1 * 0.707 * math.log(40) + 1
    assert abs(m - 1.260804) < 1e-6
    assert abs(mla_softmax_scale(cfg) * math.sqrt(192) - 1.589626) < 1e-6
    assert rope_scale(cfg.rope_scaling) == 1.0
    np.testing.assert_allclose(f, ref.yarn_inv_freq(ref.arch(
        {**json.loads((CHIP / "configs" / "deepseek_v2_lite.lora_ring8.json")
                      .read_text())})), rtol=1e-12)


def test_trunk_replays_the_reference_stream():
    """The program's seeded bfloat16 trunk is the reference's, bit for
    bit: the reference follows the same draws."""
    cfg = lm_config(ARCH)
    prog = init_trunk(cfg, SEED)
    mine = ref.trunk(A, SEED)
    np.testing.assert_array_equal(prog["embed"]["emb"], mine["embed"])
    np.testing.assert_array_equal(prog["lm_head"]["w"], mine["head"])
    lead = jax.tree.map(lambda a: a[0], prog["lead"])
    np.testing.assert_array_equal(lead["attn"]["wkv_b"],
                                  mine["layers"][0]["wkv_b"])
    np.testing.assert_array_equal(lead["mlp"]["w_down"],
                                  mine["layers"][0]["ffn"]["down"])
    moe1 = jax.tree.map(lambda a: a[1, 0], prog["stacks"]["mla_moe"])
    np.testing.assert_array_equal(moe1["moe"]["w_up"],
                                  mine["layers"][2]["w_up"])
    np.testing.assert_array_equal(moe1["moe"]["shared"]["w_gate"],
                                  mine["layers"][2]["shared"]["gate"])


def _theta(key, scale=0.05):
    """A flat adapter sample with nonzero B (the reference's layout)."""
    cfg = lm_config(ARCH)
    mean = lora_init(cfg, RANK)(key)
    from repro.core.flat import FlatLayout

    layout = FlatLayout.for_pytree(mean)
    flat = layout.flatten(mean)
    flat = flat + scale * jax.random.normal(jax.random.key(5), flat.shape)
    return layout, flat


def test_mla_logits_match_reference():
    """Logits of the whole model under sampled adapters, the program in
    float32 (its latent attention, YaRN RoPE, dropless MoE, chunked head)
    against the reference at HIGHEST: float32 round-off of a few
    summation orders, 2e-4 on logits of magnitude ~3."""
    cfg = dataclasses.replace(lm_config(ARCH), dtype="float32")
    trunk = init_trunk(cfg, SEED)
    layout, theta = _theta(jax.random.key(3))
    toks = jax.random.randint(jax.random.key(4), (L,), 0, cfg.vocab_size)
    h, counts = jax.jit(lambda tr, ads, t: mla_hidden(
        tr, cfg, t[None], adapters=ads, lora_scale=ALPHA / RANK))(
            trunk, layout.unflatten(theta), toks)
    logits = np.asarray(h[0] @ trunk["lm_head"]["w"].astype(jnp.float32))
    with jax.default_matmul_precision("highest"):
        want = np.asarray(jax.jit(lambda th, tr, t: ref.seq_logits(
            th, tr, t, A, RANK, ALPHA / RANK))(theta, ref.trunk(A, SEED),
                                                toks))
    assert np.abs(want).max() > 1.0
    np.testing.assert_allclose(logits, want, atol=2e-4, rtol=2e-4)
    assert counts.shape == (2, 8) and int(counts.sum()) == 2 * L * 2


def test_zero_b_adapters_give_the_trunk_exactly():
    """B = 0 (the adapters' initial mean): the adapted model is the trunk,
    bit for bit, whatever A is."""
    cfg = lm_config(ARCH)
    trunk = init_trunk(cfg, SEED)
    ads = lora_init(cfg, RANK)(jax.random.key(6))
    assert float(jnp.abs(ads["moe"]["q"]["a"]).max()) > 0
    toks = jax.random.randint(jax.random.key(7), (2, L), 0, cfg.vocab_size)
    with_ads, _ = jax.jit(lambda tr, a, t: mla_hidden(
        tr, cfg, t, adapters=a, lora_scale=2.0))(trunk, ads, toks)
    bare, _ = jax.jit(lambda tr, t: mla_hidden(tr, cfg, t))(trunk, toks)
    np.testing.assert_array_equal(with_ads, bare)


def _dense_moe(params, x, cfg):
    """Per token, the weighted sum of its top-k experts' SwiGLUs (each
    token's k expert weights gathered directly), plus the shared
    experts."""
    logits = x @ params["router"]
    w, idx = moe_lib.route_greedy(logits, cfg)
    g = jnp.einsum("td,tkdf->tkf", x, params["w_gate"][idx])
    u = jnp.einsum("td,tkdf->tkf", x, params["w_up"][idx])
    y = jnp.einsum("tkf,tkfd,tk->td", jax.nn.silu(g) * u,
                   params["w_down"][idx], w)
    sh = params["shared"]
    shared = (jax.nn.silu(x @ sh["w_gate"]) * (x @ sh["w_up"])) @ sh["w_down"]
    return y + shared


@pytest.mark.parametrize("uneven", [False, True])
def test_dropless_moe_matches_the_per_token_sum(uneven):
    """The grouped dispatch drops nothing: with uneven routing (one expert
    takes most tokens, far above any capacity factor) every token still
    gets its top-k experts.  Under vmap the agents' tokens fold into one
    grouped product with the same result, and gradients agree."""
    cfg = dataclasses.replace(lm_config(ARCH), dtype="float32")
    params = moe_lib.deepseek_moe_init(jax.random.key(8), cfg)
    if uneven:
        params["router"] = params["router"].at[:, 3].add(2.0)
    x = jax.random.normal(jax.random.key(9), (3, 16, cfg.d_model))
    x = x + (1.0 if uneven else 0.0)
    prog = jax.jit(jax.vmap(lambda xa: moe_lib.deepseek_moe(
        params, xa[None], cfg)))
    dense = jax.jit(jax.vmap(lambda xa: _dense_moe(params, xa, cfg)))
    y, counts = prog(x)
    want = dense(x)
    np.testing.assert_allclose(y[:, 0], want, atol=1e-4, rtol=1e-4)
    assert counts.shape == (3, 8) and int(counts.sum()) == 3 * 16 * 2
    if uneven:
        assert int(counts[:, 3].sum()) > 3 * 16 * 0.75
    loss = lambda f: lambda xx: jnp.sum(jnp.sin(f(xx)))
    g_prog = jax.jit(jax.grad(loss(lambda xx: prog(xx)[0][:, 0])))(x)
    g_want = jax.jit(jax.grad(loss(dense)))(x)
    np.testing.assert_allclose(g_prog, g_want, atol=1e-4, rtol=1e-4)


def test_chunked_loss_is_the_whole_cross_entropy():
    h = jax.random.normal(jax.random.key(10), (64, 16))
    w = jax.random.normal(jax.random.key(11), (16, 512))
    y = jax.random.randint(jax.random.key(12), (64,), 0, 500)
    lg = (h @ w)[:, :500]
    want = jnp.sum(jax.nn.logsumexp(lg, -1)
                   - jnp.take_along_axis(lg, y[:, None], -1)[:, 0])
    np.testing.assert_allclose(chunked_xent(h, y, w, 500, chunk=16), want,
                               rtol=1e-5)


# ---------------------------------------------------------------------------
# Session.round() against the reference
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("topology", [SYNC, GOSSIP], ids=["simulated",
                                                           "gossip"])
def test_session_round_matches_reference(topology, reference_readings):
    """Two rounds through ``Session.round()`` (the SimulatedEngine, or the
    GossipEngine with every edge firing) read what the reference reads,
    within the cell's committed ``LIMITS``."""
    session = build_session(_spec(topology))
    assert type(session.engine).__name__ == (
        "SimulatedEngine" if topology is SYNC else "GossipEngine")
    p = session.posterior().mean.shape
    assert p == (N, sum(x.size for x in jax.tree.leaves(
        lora_init(lm_config(ARCH), RANK)(jax.random.key(0)))))
    ok, checks = _verdict(_readings(session), reference_readings)
    assert ok, checks


def test_float8_control_fails_the_limits(reference_readings):
    control = ref.train_readings(DOC, {"clock": None}, SEED, 2,
                                 matmul_dtype=jnp.float8_e4m3fn)
    ok, checks = _verdict(control, reference_readings)
    assert not ok, checks


def _state_unchanged(monkeypatch):
    from repro.api.engines import SimulatedEngine

    orig = SimulatedEngine.run_round

    def frozen(self, state, batches, W, key):
        # the posterior and optimizer state come back unchanged; the round
        # counters advance, so the run goes on
        new, losses = orig(self, state, batches, W, key)
        return dataclasses.replace(new, posterior=state.posterior,
                                   opt_state=state.opt_state), losses

    monkeypatch.setattr(SimulatedEngine, "run_round", frozen)


def _half_batch(monkeypatch):
    import repro.api.data as data_mod

    orig = data_mod.make_lm_batch_sampler

    def halved(*args, **kw):
        sampler = orig(*args, **kw)

        def sample(key, r):
            # the first half of each sequence's positions, twice
            return {k: jnp.concatenate([v[..., :L // 2]] * 2, axis=-1)
                    for k, v in sampler(key, r).items()}

        return sample

    monkeypatch.setattr(data_mod, "make_lm_batch_sampler", halved)


def _no_exchange(monkeypatch):
    import repro.core.simulated as sim

    monkeypatch.setattr(sim, "consensus_all_agents",
                        lambda post, *a, **k: post)


PROGRAM_FAULTS = {"state_unchanged": _state_unchanged,
                  "half_batch": _half_batch, "no_exchange": _no_exchange}


@pytest.mark.parametrize("fault", sorted(PROGRAM_FAULTS))
def test_program_fault_fails_the_limits(fault, reference_readings,
                                        monkeypatch):
    """Each fault the chip cell's program can have, planted in the program
    at this size, fails a committed limit."""
    PROGRAM_FAULTS[fault](monkeypatch)
    ok, checks = _verdict(_readings(build_session(_spec(SYNC))),
                          reference_readings)
    assert not ok, checks


def test_wake_on_event_gossip_freezes_sleeping_agents():
    """Under local_policy="active" an LM agent with no incoming event keeps
    its posterior bitwise and reports no routed tokens; the others route
    every token of their u steps to top-k experts in each MoE layer."""
    topo = TopologySpec(
        kind="gossip", params={"base": "bidirectional_ring",
                               "base_params": {"n": N}},
        clock={"kind": "poisson", "rate": 0.4, "seed": 3,
               "local_policy": "active"})
    s = build_session(_spec(topo))
    post0 = s.posterior()
    win = s.spec.topology.gossip_clock().window(0)
    sleeping = ~win.active
    assert sleeping.any() and win.active.any()
    s.round()
    np.testing.assert_array_equal(np.asarray(s.posterior().mean)[sleeping],
                                  np.asarray(post0.mean)[sleeping])
    tokens = np.asarray(s.engine.last_aux["expert_tokens"]).sum(axis=(1, 2))
    np.testing.assert_array_equal(tokens[sleeping], 0)
    np.testing.assert_array_equal(tokens[win.active], U * L * 2 * 2)


def test_obs_is_a_pure_observer():
    """Training is bit-identical with observability on or off; on, the
    registry holds the router's tokens per expert and the load gauge."""
    on = build_session(_spec(SYNC, obs=True))
    off = build_session(_spec(SYNC, obs=False))
    on.round()
    off.round()
    np.testing.assert_array_equal(on.posterior().mean, off.posterior().mean)
    np.testing.assert_array_equal(on.posterior().rho, off.posterior().rho)
    reg = on.obs.registry
    hist = reg.histogram("model.expert_tokens")
    total = sum(hist.summary(layer=layer)["sum"] for layer in (0, 1))
    assert total == N * U * L * 2 * 2  # agents x steps x tokens x k x layers
    assert reg.gauge("model.expert_load_max").value() >= 1.0


def test_lm_spec_validation_and_refusals():
    with pytest.raises(ValueError, match="needs an arch"):
        InferenceSpec(model="lm").validate()
    with pytest.raises(ValueError, match="model='lm'"):
        InferenceSpec(arch=ARCH).validate()
    with pytest.raises(ValueError, match="zipf_tokens"):
        dataclasses.replace(_spec(SYNC), data=DataSpec()).validate()
    with pytest.raises(ValueError, match="launch"):
        dataclasses.replace(_spec(SYNC), run=RunSpec(engine="launch")
                            ).validate()
    session = build_session(_spec(SYNC))
    with pytest.raises(NotImplementedError, match="held-out token NLL"):
        session.evaluate()
    with pytest.raises(ValueError, match="classification"):
        session.attach_server()


# ---------------------------------------------------------------------------
# the token sampler
# ---------------------------------------------------------------------------


def test_zipf_sampler_follows_the_law_without_vocab_sized_arrays():
    """Inverse-CDF draws: the histogram of 400,000 ids matches P(k) ~
    (k + 1)^-1.2 within sampling error, and the sampler builds nothing of
    size [..., V] per token."""
    v = 1000
    sampler = make_lm_batch_sampler(v, 5, 9_999, n_agents=4,
                                    local_updates=2)
    toks = np.asarray(sampler(jax.random.key(0), 0)["tokens"])
    assert toks.shape == (4, 2, 5, 9_999)
    ids = np.asarray(make_lm_batch_sampler(v, 5, 9_999, n_agents=4,
                                           local_updates=2)(
        jax.random.key(1), 0)["targets"]).ravel()
    p = np.diff(np.concatenate([[0.0], zipf_cdf(v).astype(np.float64)]))
    w = 1.0 / np.arange(1, v + 1) ** 1.2
    np.testing.assert_allclose(p, w / w.sum(), rtol=1e-4, atol=1e-7)
    counts = np.bincount(ids, minlength=v)
    n = ids.size
    for k in (0, 1, 2, 5, 10, 50, 100):
        sd = math.sqrt(n * p[k] * (1 - p[k]))
        assert abs(counts[k] - n * p[k]) < 5 * sd, (k, counts[k], n * p[k])
    tail = counts[500:].sum() / n
    assert abs(tail - p[500:].sum()) < 5 * math.sqrt(p[500:].sum() / n)
    jaxpr = jax.make_jaxpr(lambda k: sampler(k, 0))(jax.random.key(0))
    for shp in _shapes(jaxpr.jaxpr):
        assert not (len(shp) > 1 and shp[-1] == v), shp


def _shapes(jaxpr):
    """Every intermediate's shape, nested jaxprs included."""
    for eqn in jaxpr.eqns:
        for var in eqn.outvars:
            yield getattr(var.aval, "shape", ())
        for param in eqn.params.values():
            inner = getattr(param, "jaxpr", None)
            if inner is not None:
                yield from _shapes(getattr(inner, "jaxpr", inner))

"""Plain reference for the ``deepseek_v2_lite.*`` configurations.

Decentralized Bayes-by-Backprop over low-rank adapters of a frozen
DeepSeek-V2-Lite trunk (arXiv:2405.04434 §2.1-2.2; BLoB, arXiv:2406.11675),
written out in straightforward ``jax.numpy`` from the published equations,
importing nothing of the system under test:

* trunk: the model's seeded random weights, stored bfloat16 and upcast to
  float32 exactly where they are used; every matmul at
  ``Precision.HIGHEST``;
* MLA without query compression: q = x W_q in heads of (nope, rope);
  [c_kv, k_pe] = x W_kv_a, c_kv RMS-normed; [k_nope, v] = c_kv W_kv_b;
  YaRN RoPE (rotate-half pairs) on q_pe and the shared k_pe; causal softmax
  at (nope + rope)^-1/2 times mscale(factor, mscale_all_dim)^2; o W_o;
* DeepSeekMoE: softmax router scores, greedy top-k, weights not
  renormalized, times the scaling factor; each token's output is the
  weighted sum of its top-k experts' SwiGLUs, computed by evaluating every
  expert on every token and weighting by the token's routing weights (zero
  outside its top-k): no sort, no grouping, no capacity; plus the shared
  experts; the leading layers are dense SwiGLUs;
* adapters: x W + (alpha / r) (x A) B on W_q, W_kv_a, W_kv_b and W_o of
  every layer; a mean-field Gaussian over A and B per agent;
* local phase: ``u`` Adam steps on ``kl_scale * KL(q || prior) +`` the
  summed next-token cross-entropy of one MC sample, the prior being the
  agent's posterior at the start of the round; then eq. (6) over the
  round's W (a bidirectional ring, weights 1/3).

Where the semantics are a seeded random stream (the trunk, the adapters'
initial means, the tokens, the Monte-Carlo noise) the reference draws the
same stream from the run's seed with the same ``jax.random`` calls, so
that one seed gives one trajectory.  The reference takes no array from the
program.  It computes one agent and one sequence at a time, so that it fits
on the chip beside nothing else.

``matmul_dtype`` selects the control: the trunk's matmuls take inputs
rounded to that dtype (float8) and accumulate float32.  ``fault`` plants
one fault: ``half_batch`` (the loss of the first half of each sequence's
positions, doubled), ``no_exchange`` (no eq. (6)), ``renorm`` (top-k
weights renormalized) or ``no_mscale`` (the plain softmax scale, YaRN's
mscale^2 left out).  What it does not implement (query compression,
another router, clock, optimizer, graph or wire precision) is refused.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST
PROJ = ("q", "kv_a", "kv_b", "o")  # the adapters' projections
FAULTS = ("half_batch", "no_exchange", "renorm", "no_mscale")
EXPERT_BLOCK = 16  # experts evaluated together on every token

# ---------------------------------------------------------------------------
# the architecture, from the configuration's published keys
# ---------------------------------------------------------------------------


def arch(cfg: dict) -> dict:
    rs = cfg["rope_scaling"]
    return dict(
        d=cfg["hidden_size"], h=cfg["num_attention_heads"],
        nope=cfg["qk_nope_head_dim"], rope=cfg["qk_rope_head_dim"],
        v=cfg["v_head_dim"], c=cfg["kv_lora_rank"],
        e=cfg["n_routed_experts"], k=cfg["num_experts_per_tok"],
        f=cfg["moe_intermediate_size"],
        f_shared=cfg["n_shared_experts"] * cfg["moe_intermediate_size"],
        d_ff=cfg["intermediate_size"], vocab=cfg["vocab_size"],
        eps=cfg["rms_norm_eps"], theta=float(cfg["rope_theta"]),
        layers=cfg["n_layers"], dense=cfg["first_k_dense_replace"],
        norm_topk=cfg["norm_topk_prob"],
        scaling=float(cfg["routed_scaling_factor"]),
        yarn=dict(factor=float(rs["factor"]),
                  orig=int(rs["original_max_position_embeddings"]),
                  beta_fast=float(rs["beta_fast"]),
                  beta_slow=float(rs["beta_slow"]),
                  mscale=float(rs["mscale"]),
                  mscale_all_dim=float(rs["mscale_all_dim"])))


def proj_dims(a: dict) -> dict:
    return {"q": (a["d"], a["h"] * (a["nope"] + a["rope"])),
            "kv_a": (a["d"], a["c"] + a["rope"]),
            "kv_b": (a["c"], a["h"] * (a["nope"] + a["v"])),
            "o": (a["h"] * a["v"], a["d"])}


def yarn_mscale(factor: float, mscale: float) -> float:
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def yarn_inv_freq(a: dict) -> np.ndarray:
    """DeepSeek-V2's YaRN frequencies over the rope dims (float64)."""
    y, dim, base = a["yarn"], a["rope"], a["theta"]
    freq_extra = 1.0 / base ** (np.arange(0, dim, 2) / dim)
    freq_inter = freq_extra / y["factor"]

    def corr(rot):
        return dim * math.log(y["orig"] / (rot * 2 * math.pi)) / (
            2 * math.log(base))

    low = max(math.floor(corr(y["beta_fast"])), 0)
    high = min(math.ceil(corr(y["beta_slow"])), dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dim // 2) - low) / (high - low), 0, 1)
    return freq_inter * ramp + freq_extra * (1 - ramp)


def softmax_scale(a: dict, fault=None) -> float:
    scale = (a["nope"] + a["rope"]) ** -0.5
    y = a["yarn"]
    if fault != "no_mscale" and y["mscale_all_dim"]:
        scale *= yarn_mscale(y["factor"], y["mscale_all_dim"]) ** 2
    return scale


# ---------------------------------------------------------------------------
# the seeded trunk (bfloat16), as the program draws it
# ---------------------------------------------------------------------------


def _tn(key, shape):
    """N(0, 1/fan_in) truncated at two standard deviations."""
    return (1.0 / jnp.sqrt(shape[-2])) * jax.random.truncated_normal(
        key, -2.0, 2.0, shape, jnp.float32)


def _swiglu_w(key, d, f):
    k1, k2, k3 = jax.random.split(key, 3)
    return {"gate": _tn(k1, (d, f)), "up": _tn(k2, (d, f)),
            "down": _tn(k3, (f, d))}


@functools.partial(jax.jit, static_argnums=(1, 2))
def _layer(key, a_items, moe):
    a = dict(a_items)
    k_attn, k_ffn = jax.random.split(key)
    ka = jax.random.split(k_attn, 4)
    dims = proj_dims(a)
    p = {f"w{n}": _tn(k, dims[n]) for k, n in zip(ka, PROJ)}
    if moe:
        km = jax.random.split(k_ffn, 5)
        e, d, f = a["e"], a["d"], a["f"]
        p.update(router=_tn(km[0], (d, e)), w_gate=_tn(km[1], (e, d, f)),
                 w_up=_tn(km[2], (e, d, f)), w_down=_tn(km[3], (e, f, d)))
        if a["f_shared"]:
            p["shared"] = _swiglu_w(km[4], d, a["f_shared"])
    else:
        p["ffn"] = _swiglu_w(k_ffn, a["d"], a["d_ff"])
    return jax.tree.map(lambda x: x.astype(jnp.bfloat16), p)


def trunk(a: dict, seed: int) -> dict:
    """Embedding, head and layers from ``fold_in(key(seed), 1)``: split in
    8; embedding N(0, 0.02^2) from the first, head from the second; the
    MoE layers from the split of the split of the third, the leading dense
    layers from the split of the seventh.  RMSNorm scales are ones."""
    ks = jax.random.split(jax.random.fold_in(jax.random.key(seed), 1), 8)
    items = tuple(sorted((k, v) for k, v in a.items() if k != "yarn"))
    n_moe = a["layers"] - a["dense"]
    lead = jax.random.split(ks[6], a["dense"]) if a["dense"] else []
    moe = jax.random.split(jax.random.split(ks[2], 1)[0], n_moe)
    bf = jnp.bfloat16
    return {
        "embed": (jax.random.normal(ks[0], (a["vocab"], a["d"]), jnp.float32)
                  * 0.02).astype(bf),
        "head": _tn(ks[1], (a["d"], a["vocab"])).astype(bf),
        "layers": ([_layer(k, items, False) for k in lead]
                   + [_layer(k, items, True) for k in moe]),
    }


# ---------------------------------------------------------------------------
# adapters and the flat posterior
# ---------------------------------------------------------------------------


def lora_leaves(a: dict, rank: int) -> list:
    """(stack, projection, 'a' or 'b', shape) in the flat order: stacks,
    projections and a/b each sorted by name."""
    stacks = {"lead": a["dense"], "moe": a["layers"] - a["dense"]}
    dims = proj_dims(a)
    out = []
    for stack in sorted(s for s, n in stacks.items() if n):
        for proj in sorted(PROJ):
            d_in, d_out = dims[proj]
            out.append((stack, proj, "a", (stacks[stack], d_in, rank)))
            out.append((stack, proj, "b", (stacks[stack], rank, d_out)))
    return out


def leaf_slices(a: dict, rank: int) -> dict:
    out, off = {}, 0
    for stack, proj, ab, shape in lora_leaves(a, rank):
        size = int(np.prod(shape))
        out[f"{stack}.{proj}.{ab}"] = (off, off + size, shape)
        off += size
    return out


def init_mean(key, a: dict, rank: int) -> jax.Array:
    """A ~ N(0, 1/d_in), B = 0: one key per (stack, projection), the stacks
    (lead, then moe) and projections (q, kv_a, kv_b, o) in that order."""
    stacks = [s for s, n in (("lead", a["dense"]),
                             ("moe", a["layers"] - a["dense"])) if n]
    keys = iter(jax.random.split(key, len(stacks) * len(PROJ)))
    dims = proj_dims(a)
    parts = {}
    for stack in stacks:
        n = a["dense"] if stack == "lead" else a["layers"] - a["dense"]
        for proj in PROJ:
            d_in, d_out = dims[proj]
            parts[f"{stack}.{proj}.a"] = jax.random.normal(
                next(keys), (n, d_in, rank)) / np.sqrt(d_in)
            parts[f"{stack}.{proj}.b"] = jnp.zeros((n, rank, d_out))
    return jnp.concatenate([parts[k].reshape(-1)
                            for k in leaf_slices(a, rank)])


def adapters_of(theta, a: dict, rank: int) -> list:
    """Flat theta [P] -> one {proj: (A, B)} per layer."""
    sl = leaf_slices(a, rank)
    out = []
    for layer in range(a["layers"]):
        stack, i = (("lead", layer) if layer < a["dense"]
                    else ("moe", layer - a["dense"]))
        out.append({p: tuple(theta[s:e].reshape(shape)[i]
                             for s, e, shape in (sl[f"{stack}.{p}.a"],
                                                 sl[f"{stack}.{p}.b"]))
                    for p in PROJ})
    return out


# ---------------------------------------------------------------------------
# the forward pass and the loss of one sequence
# ---------------------------------------------------------------------------


def _mm(x, w, mdt):
    """x @ w in float32 at HIGHEST; the control rounds both inputs to
    ``mdt`` first."""
    w = w.astype(jnp.float32)
    if mdt is not None:
        x = x.astype(mdt).astype(jnp.float32)
        w = w.astype(mdt).astype(jnp.float32)
    return jnp.matmul(x, w, precision=HI)


def _rms(x, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True) + eps)


def _rope(x, inv_freq):
    """Rotate-half RoPE over the last axis of x [L, ..., rope]."""
    ang = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None] * inv_freq
    ang = ang.reshape((x.shape[0],) + (1,) * (x.ndim - 2) + (-1,))
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _swiglu(x, w, mdt):
    return _mm(jax.nn.silu(_mm(x, w["gate"], mdt)) * _mm(x, w["up"], mdt),
               w["down"], mdt)


def _attention(p, ad, x, a, scale, mdt, fault):
    seq, h, nope, rd = x.shape[0], a["h"], a["nope"], a["rope"]

    def proj(name, inp):
        a_, b_ = ad[name]
        return _mm(inp, p[f"w{name}"], mdt) + scale * jnp.matmul(
            jnp.matmul(inp, a_, precision=HI), b_, precision=HI)

    q = proj("q", x).reshape(seq, h, nope + rd)
    ckv = proj("kv_a", x)
    kv = proj("kv_b", _rms(ckv[:, :a["c"]], a["eps"])).reshape(
        seq, h, nope + a["v"])
    inv_freq = jnp.asarray(yarn_inv_freq(a), jnp.float32)
    q_pe = _rope(q[..., nope:], inv_freq)
    k_pe = _rope(ckv[:, a["c"]:], inv_freq)
    s = (jnp.einsum("qhd,khd->hqk", q[..., :nope], kv[..., :nope],
                    precision=HI)
         + jnp.einsum("qhd,kd->hqk", q_pe, k_pe, precision=HI))
    s = s * softmax_scale(a, fault)
    causal = jnp.arange(seq)[:, None] >= jnp.arange(seq)[None, :]
    pr = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
    o = jnp.einsum("hqk,khd->qhd", pr, kv[..., nope:], precision=HI)
    return proj("o", o.reshape(seq, h * a["v"]))


def _moe(p, x, a, mdt, fault):
    probs = jax.nn.softmax(jnp.matmul(x, p["router"].astype(jnp.float32),
                                      precision=HI), axis=-1)
    w, idx = jax.lax.top_k(probs, a["k"])
    if fault == "renorm":
        w = w / jnp.sum(w, -1, keepdims=True)
    w = w * a["scaling"]
    weight = jnp.zeros_like(probs).at[
        jnp.arange(x.shape[0])[:, None], idx].set(w)  # [L, E], top-k only
    cast = (lambda t: t) if mdt is None else (
        lambda t: t.astype(mdt).astype(jnp.float32))

    @jax.checkpoint  # one block of experts at a time, forward and backward
    def experts(x, weight, w_gate, w_up, w_down):
        xe = cast(x)
        g = jnp.einsum("td,edf->tef", xe, cast(w_gate.astype(jnp.float32)),
                       precision=HI)
        u = jnp.einsum("td,edf->tef", xe, cast(w_up.astype(jnp.float32)),
                       precision=HI)
        hid = cast(jax.nn.silu(g) * u * weight[:, :, None])
        return jnp.einsum("tef,efd->td", hid,
                          cast(w_down.astype(jnp.float32)), precision=HI)

    block = EXPERT_BLOCK if a["e"] % EXPERT_BLOCK == 0 else a["e"]
    y = sum(experts(x, weight[:, i:i + block],
                    *(p[w][i:i + block] for w in ("w_gate", "w_up", "w_down")))
            for i in range(0, a["e"], block))
    if "shared" in p:
        y = y + _swiglu(x, p["shared"], mdt)
    return y


def seq_logits(theta, tr, tokens, a, rank, lora_scale, mdt=None,
               fault=None):
    """Next-token logits [L, V] of one sequence under the adapters
    ``theta`` (flat) on the trunk ``tr``."""
    ads = adapters_of(theta, a, rank)
    x = tr["embed"][tokens].astype(jnp.float32)
    for i, (p, ad) in enumerate(zip(tr["layers"], ads)):
        def layer(x, p, ad, moe=i >= a["dense"]):
            x = x + _attention(p, ad, _rms(x, a["eps"]), a, lora_scale, mdt,
                               fault)
            h = _rms(x, a["eps"])
            return x + (_moe(p, h, a, mdt, fault) if moe
                        else _swiglu(h, p["ffn"], mdt))
        x = jax.checkpoint(layer)(x, p, ad)
    return _mm(_rms(x, a["eps"]), tr["head"], mdt)


def seq_nll(theta, tr, tokens, targets, a, rank, lora_scale, mdt=None,
            fault=None):
    """Summed next-token cross-entropy of one sequence."""
    logits = seq_logits(theta, tr, tokens, a, rank, lora_scale, mdt, fault)
    nll = jax.nn.logsumexp(logits, -1) - jnp.take_along_axis(
        logits, targets[:, None], -1)[:, 0]
    if fault == "half_batch":
        # half of the positions left out, the sum scaled to the whole
        return 2.0 * jnp.sum(nll[: nll.shape[0] // 2])
    return jnp.sum(nll)


# ---------------------------------------------------------------------------
# the data stream and the training
# ---------------------------------------------------------------------------


def zipf_cdf(vocab: int, exponent: float) -> np.ndarray:
    w = 1.0 / np.arange(1, vocab + 1, dtype=np.float64) ** exponent
    return (np.cumsum(w) / w.sum()).astype(np.float32)


def draw_tokens(key, cdf, shape):
    """Zipf ids by inverse CDF over uniforms (id k has P proportional to
    (k + 1)^-exponent)."""
    u = jax.random.uniform(key, shape, jnp.float32)
    return jnp.minimum(jnp.searchsorted(cdf, u, side="right"),
                       cdf.shape[0] - 1).astype(jnp.int32)


def softplus_inv(y):
    return jnp.log(jnp.expm1(y))


def kl(mq, rq, mp, rp):
    sq, sp = jax.nn.softplus(rq), jax.nn.softplus(rp)
    return jnp.sum(jnp.log(sp / sq)
                   + (jnp.square(sq) + jnp.square(mq - mp))
                   / (2.0 * jnp.square(sp)) - 0.5)


def ring_w(n: int) -> np.ndarray:
    w = np.zeros((n, n))
    for i in range(n):
        for j in (i - 1, i, i + 1):
            w[i, j % n] += 1.0 / 3.0
    return w


SUPPORTED = {("model", "name"): "lm", ("data", "dataset"): "zipf_tokens",
             ("data", "partition"): "iid",
             ("inference", "optimizer"): "adam",
             ("inference", "wire_dtype"): "f32",
             ("inference", "shared_init"): True,
             ("topology", "graph"): "bidirectional_ring"}
INFERENCE_KEYS = ("lora_rank", "lora_alpha", "optimizer", "lr", "lr_decay",
                  "kl_scale", "init_sigma", "shared_init", "n_mc_samples",
                  "wire_dtype")


DATASET_KEYS = ("vocab_size", "seq_len", "exponent")


def check_supported(cfg: dict, traffic: dict) -> None:
    for (section, key), want in SUPPORTED.items():
        if cfg[section].get(key) != want:
            raise ValueError(f"the reference implements {section}.{key} = "
                             f"{want!r}, not {cfg[section].get(key)!r}")
    extra = sorted(set(cfg["inference"]) - set(INFERENCE_KEYS))
    if extra:
        raise ValueError(f"the reference does not implement inference {extra}")
    for section, keys in (("dataset_params", DATASET_KEYS),
                          ("partition_params", ())):
        extra = sorted(set(cfg["data"][section]) - set(keys))
        if extra:
            raise ValueError(f"the reference does not implement data "
                             f"{section} {extra}")
    if set(cfg["topology"]["params"]) != {"n"} or (
            cfg["topology"]["params"]["n"] != cfg["n_agents"]):
        raise ValueError("the reference's ring takes one parameter, n = "
                         "n_agents")
    if cfg["q_lora_rank"] is not None:
        raise ValueError("the reference has no query compression")
    if (cfg["scoring_func"], cfg["topk_method"], cfg["hidden_act"]) != (
            "softmax", "greedy", "silu") or cfg["rope_scaling"]["type"] != "yarn":
        raise ValueError("the reference implements softmax scoring, greedy "
                         "top-k, SiLU and YaRN only")
    if cfg["tie_word_embeddings"] or cfg["attention_bias"]:
        raise ValueError("the reference has untied embeddings, no biases")
    if cfg["vocab_size"] % 256:
        raise ValueError("the program pads the vocabulary to 256; the "
                         "reference does not")
    if traffic.get("clock") is not None:
        raise ValueError("the reference runs synchronous rounds only")


class Trainer:
    """The reference network: per-agent state, one agent and one sequence
    at a time."""

    def __init__(self, cfg: dict, traffic: dict, seed: int, *,
                 matmul_dtype=None, fault: str | None = None):
        check_supported(cfg, traffic)
        if fault not in (None,) + FAULTS:
            raise ValueError(f"unknown fault {fault!r}")
        self.a = a = arch(cfg)
        inf, data = cfg["inference"], cfg["data"]
        self.inf, self.fault = inf, fault
        self.rank = inf["lora_rank"]
        self.lora_scale = inf["lora_alpha"] / inf["lora_rank"]
        self.u, self.bsz = data["local_updates"], data["batch_size"]
        dp = data["dataset_params"]
        self.seq = dp["seq_len"]
        self.cdf = jnp.asarray(zipf_cdf(dp["vocab_size"],
                                        dp.get("exponent", 1.2)))
        self.n = cfg["n_agents"]
        self.w = jnp.asarray(ring_w(self.n), jnp.float32)
        self.trunk = trunk(a, seed)
        key, k_init = jax.random.split(jax.random.key(seed))
        self.key = key
        self.mean0 = init_mean(k_init, a, self.rank)
        self.rho0 = float(np.log(np.expm1(inf["init_sigma"])))
        p = self.mean0.shape[0]
        z = jnp.zeros((p,), jnp.float32)
        self.state = [dict(m=self.mean0, r=jnp.full((p,), self.rho0),
                           mu_m=z, mu_r=z, nu_m=z, nu_r=z, step=0)
                      for _ in range(self.n)]
        self.round_idx = 0
        self._step = self._make_step(matmul_dtype)

    def _make_step(self, mdt):
        a, rank, scale, fault = self.a, self.rank, self.lora_scale, self.fault
        n_mc, kl_scale = self.inf["n_mc_samples"], self.inf["kl_scale"]

        def free_energy(m, r, pm, pr, tr, toks, tgts, key):
            def one(k):
                theta = m + jax.nn.softplus(r) * jax.random.normal(
                    k, m.shape, jnp.float32)
                return sum(seq_nll(theta, tr, toks[b], tgts[b], a, rank,
                                   scale, mdt, fault)
                           for b in range(toks.shape[0]))

            keys = jax.random.split(key, n_mc)
            return kl_scale * kl(m, r, pm, pr) + sum(
                one(keys[i]) for i in range(n_mc)) / n_mc

        return jax.jit(jax.value_and_grad(free_energy, argnums=(0, 1)))

    def round(self) -> float:
        """One round; returns the mean over agents of their mean step loss."""
        b1, b2, eps = 0.9, 0.999, 1e-8
        self.key, k_batch, k_round = jax.random.split(self.key, 3)
        toks = draw_tokens(k_batch, self.cdf,
                           (self.n, self.u, self.bsz, self.seq + 1))
        akeys = jax.random.split(k_round, self.n)
        lr = jnp.float32(self.inf["lr"]) * jnp.float32(
            self.inf["lr_decay"]) ** jnp.float32(self.round_idx)
        losses = []
        for i, st in enumerate(self.state):
            pm, pr = st["m"], st["r"]
            steps = []
            for t, k in enumerate(jax.random.split(akeys[i], self.u)):
                loss, (gm, gr) = self._step(
                    st["m"], st["r"], pm, pr, self.trunk,
                    toks[i, t, :, :-1], toks[i, t, :, 1:], k)
                st["step"] += 1
                bc1 = 1.0 - b1 ** jnp.float32(st["step"])
                bc2 = 1.0 - b2 ** jnp.float32(st["step"])
                for par, g, mu, nu in (("m", gm, "mu_m", "nu_m"),
                                       ("r", gr, "mu_r", "nu_r")):
                    st[mu] = b1 * st[mu] + (1 - b1) * g
                    st[nu] = b2 * st[nu] + (1 - b2) * jnp.square(g)
                    st[par] = st[par] - lr * (st[mu] / bc1) / (
                        jnp.sqrt(st[nu] / bc2) + eps)
                steps.append(float(loss))
            losses.append(np.mean(steps))
        if self.fault != "no_exchange":
            self._consensus()
        self.round_idx += 1
        return float(np.mean(losses))

    def _consensus(self) -> None:
        m = jnp.stack([st["m"] for st in self.state])
        r = jnp.stack([st["r"] for st in self.state])
        prec = 1.0 / jnp.square(jax.nn.softplus(r))
        new_prec = jnp.matmul(self.w, prec, precision=HI)
        new_m = jnp.matmul(self.w, prec * m, precision=HI) / new_prec
        new_r = softplus_inv(jax.lax.rsqrt(new_prec))
        for i, st in enumerate(self.state):
            st["m"], st["r"] = new_m[i], new_r[i]

    def leaf_norms(self, which: str) -> dict:
        """Norm over all agents of each adapter leaf (its full path) of
        ``which``: ``grad`` (Adam's first moment, mean and rho) or
        ``change`` (posterior minus the initial posterior)."""
        sl = leaf_slices(self.a, self.rank)
        sq = {}
        for st in self.state:
            if which == "grad":
                parts = {"mean": st["mu_m"], "rho": st["mu_r"]}
            else:
                parts = {"mean": st["m"] - self.mean0,
                         "rho": st["r"] - jnp.float32(self.rho0)}
            for kind, arr in parts.items():
                for name, (s, e, _) in sl.items():
                    key = f"{kind}.{name}"
                    sq[key] = sq.get(key, 0.0) + float(
                        jnp.sum(jnp.square(arr[s:e])))
        return {k: float(np.sqrt(v)) for k, v in sq.items()}


def train_readings(cfg: dict, traffic: dict, seed: int, rounds: int, *,
                   matmul_dtype=None, fault: str | None = None) -> dict:
    """Per-round losses, per-leaf norms of Adam's first moment after round
    1, and per-leaf norms of the posterior's change after ``rounds``."""
    with jax.default_matmul_precision("highest"):
        tr = Trainer(cfg, traffic, seed, matmul_dtype=matmul_dtype,
                     fault=fault)
        losses = [tr.round()]
        grad = tr.leaf_norms("grad")
        losses += [tr.round() for _ in range(rounds - 1)]
        change = tr.leaf_norms("change")
    return {"loss": losses, "grad": grad, "change": change}

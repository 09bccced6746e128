"""``build_session(spec)`` — the supported front door.

Validates an ``ExperimentSpec`` eagerly (topology connectivity and
row-stochasticity, agent-count agreement between topology and partition,
dataset/model shape agreement by construction), builds the data and the
engine, and returns a ``Session``:

    spec = ExperimentSpec(
        topology=TopologySpec.star(n_edge=3, a=0.5),
        data=DataSpec(partition="star", partition_params=...),
        inference=InferenceSpec(hidden=32),
        run=RunSpec(n_rounds=20, seed=0),
    )
    session = build_session(spec)
    session.run()                    # the whole experiment, or
    session.round()                  # one communication round at a time
    session.evaluate()               # per-agent test metrics (MC predictive)
    session.save("exp.ckpt")         # self-describing: spec embedded
    session = Session.load("exp.ckpt")   # rebuild + resume

The engine behind the session is swappable without touching the loop:
``simulated`` (the flat vmap runtime, ``RunSpec.engine``'s default),
``gossip`` (selected by a gossip topology) and the conjugate
linear-regression engine (selected by ``InferenceSpec.method``).
"""
from __future__ import annotations

import contextlib
import dataclasses
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np

from repro.api.data import DataBundle, build_data
from repro.api.engines import (
    ConjugateLinregEngine,
    Engine,
    SimulatedEngine,
)
from repro.api.models import ModelFns, build_model
from repro.api.spec import ExperimentSpec
from repro.core.simulated import as_w_schedule
from repro.gossip.engine import GossipEngine
from repro.vi.bayes_by_backprop import mc_predict


def build_session(spec: ExperimentSpec) -> "Session":
    """Validate ``spec`` eagerly and return a ready-to-run ``Session``."""
    spec.validate()
    n_agents = spec.topology.n_agents()
    data = build_data(spec.data, n_agents)

    model: ModelFns | None = None
    if spec.inference.method == "conjugate_linreg":
        engine: Engine = ConjugateLinregEngine(spec, data)
    else:
        model = build_model(spec.inference, data, spec.run.seed)
        if spec.topology.kind == "gossip" or (
            spec.topology.kind == "sparse"
            and spec.topology.clock is not None
        ):
            # a gossip topology IS an execution model: one event window per
            # round on the GossipEngine (validate() already rejected other
            # explicit engine choices).  A sparse topology with a clock is
            # the edge-native form of the same thing — SparseWindow streams
            # executed through consensus_flat_segments.
            engine = GossipEngine(spec, model, n_agents)
        else:
            engine = SimulatedEngine(spec, model, n_agents)
    if (spec.inference.wire_dtype == "f16"
            and jax.default_backend() == "tpu"
            and getattr(engine, "pallas_consensus", True)):
        raise ValueError(
            "wire_dtype='f16' cannot run on a TPU here: this session's "
            "consensus is a Pallas kernel, and Mosaic cannot compile its f16 "
            "round trip (tpu.pack_subelements); use wire_dtype='bf16' or "
            "'f32' (the XLA executions, consensus_impl='segments' or "
            "'ppermute', do run f16)"
        )

    key = jax.random.key(spec.run.seed)
    key, k_init = jax.random.split(key)
    state = engine.init(k_init)
    obs = None
    if spec.obs.enabled:
        from repro.obs import Observability

        obs = Observability.from_spec(spec)
        # engines expose a host-side hook; attaching is a pure-observer
        # operation (the engine only reads it at dispatch boundaries)
        engine.obs = obs
    return Session(
        spec=spec,
        engine=engine,
        model=model,
        data=data,
        state=state,
        key=key,
        round_idx=0,
        _obs=obs,
    )


_NO_SPAN = contextlib.nullcontext()


def _span(obs, name: str, **attrs):
    """A tracer span when observability is on, else a shared no-op
    context (one ``is None`` check on the uninstrumented path)."""
    return obs.tracer.span(name, **attrs) if obs is not None else _NO_SPAN


@dataclasses.dataclass
class Session:
    """A running experiment: engine-backed state + the round loop."""

    spec: ExperimentSpec
    engine: Engine
    model: ModelFns | None
    data: DataBundle
    state: Any
    key: jax.Array
    round_idx: int = 0
    history: list = dataclasses.field(default_factory=list)
    _w_schedule: Any = dataclasses.field(default=None, repr=False)
    _serve_store: Any = dataclasses.field(default=None, repr=False)
    _server: Any = dataclasses.field(default=None, repr=False)
    _obs: Any = dataclasses.field(default=None, repr=False)

    @property
    def obs(self):
        """The session's ``repro.obs.Observability`` bundle (registry,
        tracer, convergence tracker), or ``None`` when ``spec.obs`` is
        disabled — the default, in which case nothing is recorded and the
        run is bitwise identical to an uninstrumented build."""
        return self._obs

    def _spec_w_schedule(self):
        """The topology's round-indexed W callable, materialized once (the
        schedule list can be expensive to rebuild every round)."""
        if self._w_schedule is None:
            self._w_schedule = self.spec.topology.w_schedule()
        return self._w_schedule

    # -- the loop ------------------------------------------------------------

    def round(self, W=None) -> dict:
        """One communication round (u local steps + consensus).  Returns
        ``{"round", "loss", "n_trained"}``; ``W`` overrides the spec
        topology for this round only (ad-hoc time-varying experiments).

        ``n_trained`` counts agents reporting a finite loss.  Engines whose
        per-agent losses use NaN as a "did not train this round" sentinel
        (gossip wake-on-event) aggregate over the trained agents only, and
        an ALL-IDLE window (a zero-event window under
        ``local_policy="active"``) reports ``loss=None`` / ``n_trained=0``
        instead of silently writing NaN into the history; for the
        synchronous engines a NaN loss stays a loud NaN (divergence
        signal).

        Fault-aware engines (a gossip clock with a ``"faults"`` model)
        additionally report ``n_crashed`` — agents down this window.  A
        crashed agent skips local training, so its NaN sentinel loss is
        already excluded from the ``loss`` mean like any idle agent's.

        With observability enabled (``spec.obs``) the round is wrapped in a
        ``session.round`` tracer span — END-TO-END accurate wall clock (the
        loss materialization below synchronizes with the device) with
        compile-vs-warm attribution from the engine's retrace counter — and
        the loop counters/gauges land in the metrics registry.  All of it
        observes values this method computes anyway: the training math is
        identical either way (pinned by tests/test_obs.py)."""
        obs = self._obs
        if obs is None:
            return self._round_impl(W)
        tr = obs.tracer
        n_traces0 = getattr(self.engine, "n_traces", None)
        first = obs.registry.counter("session.rounds").value() == 0
        with tr.span("session.round", round=self.round_idx):
            rec = self._round_impl(W)
        if tr.enabled and tr.spans:
            retraced = (n_traces0 is not None
                        and getattr(self.engine, "n_traces") > n_traces0)
            if retraced or (n_traces0 is None and first):
                tr.spans[-1].attrs["compile"] = True
        self._obs_after_round(rec)
        return rec

    def _round_impl(self, W=None) -> dict:
        r = self.round_idx
        if W is None:
            with _span(self._obs, "session.w_build", round=r):
                W = self._spec_w_schedule()(r)
        self.key, k_batch, k_round = jax.random.split(self.key, 3)
        with _span(self._obs, "session.batches", round=r):
            batches = self.data.sampler(k_batch, r)
        # engines that declare wants_host_w take the schedule value VERBATIM
        # (the GossipEngine: host float64 w_eff for the exact active-mask /
        # f64 schedule-identity checks, or a SparseWindow object on the
        # edge-native path — jnp.asarray would round to f32 / reject it);
        # they cast to the device themselves, after the host-side work
        w_arg = (W if getattr(self.engine, "wants_host_w", False)
                 else jnp.asarray(W))
        self.state, losses = self.engine.run_round(
            self.state, batches, w_arg, k_round
        )
        self.round_idx = r + 1
        # the host's wait for the device: reading the losses back blocks
        # until the round's program has run
        with _span(self._obs, "session.sync", round=r):
            losses = np.asarray(losses)
            n_trained = int(np.isfinite(losses).sum())
            if getattr(self.engine, "loss_nan_is_sentinel", False):
                loss = float(np.nanmean(losses)) if n_trained else None
            else:
                loss = float(losses.mean())
        rec = {"round": self.round_idx, "loss": loss, "n_trained": n_trained}
        crashed = getattr(self.engine, "last_crashed", None)
        if crashed is not None:
            rec["n_crashed"] = int(np.asarray(crashed).sum())
        return rec

    def _obs_after_round(self, rec: dict) -> None:
        """Post-round registry/convergence bookkeeping (obs enabled only).
        Pure observer: reads ``rec`` and (on convergence-sample rounds) the
        posterior buffers."""
        obs = self._obs
        reg = obs.registry
        reg.counter("session.rounds", "communication rounds run").inc()
        reg.gauge("session.n_trained", "agents trained last round").set(
            rec["n_trained"]
        )
        if rec["loss"] is not None:
            reg.gauge("session.loss", "mean trained-agent loss").set(
                rec["loss"]
            )
            reg.histogram("session.loss_dist", "per-round loss").observe(
                rec["loss"]
            )
        aux = getattr(self.engine, "last_aux", None) or {}
        if "expert_tokens" in aux:
            self._obs_expert_tokens(reg, aux["expert_tokens"])
        if self.spec.inference.model == "lm":
            self._obs_attention(reg)
        if "n_crashed" in rec:
            reg.counter(
                "session.crashed_agent_windows", "agent-windows down"
            ).inc(rec["n_crashed"])
        conv = obs.convergence
        if conv is not None and (
            (rec["round"] - 1) % obs.spec.convergence_every == 0
        ):
            with obs.tracer.span("obs.convergence", round=rec["round"]):
                stats = conv.update(self.posterior(), rec["round"])
            reg.ingest("convergence", stats)

    def _obs_expert_tokens(self, reg, tokens) -> None:
        """The round's tokens per routed expert (the router's counts, the
        round's aux ``expert_tokens``, [N, n_moe, E]), summed over agents:
        one ``model.expert_tokens{layer}`` observation per expert, and the
        gauge ``model.expert_load_max``: the largest expert's count over
        the mean, the worst layer.  Then the routed experts' execution
        (``_obs_experts``)."""
        counts = np.asarray(tokens).sum(axis=0)  # [n_moe, E]
        hist = reg.histogram("model.expert_tokens",
                             "tokens per routed expert per round")
        for layer, row in enumerate(counts):
            for c in row:
                hist.observe(float(c), layer=layer)
        load = counts.max(axis=1) / np.maximum(counts.mean(axis=1), 1e-30)
        reg.gauge("model.expert_load_max", "largest expert's tokens over "
                  "the mean, worst layer, last round").set(float(load.max()))
        self._obs_experts(reg, counts)

    def _obs_experts(self, reg, counts) -> None:
        """The routed experts' grouped products, as ``_grouped_swiglu``
        decides them at trace time from the backend and the product's
        shape (every agent's and MC sample's (token, expert) pairs of one
        step, folded into one product): counter
        ``model.expert_matmul_path{path=pallas_gmm|ragged_dot}`` once a
        round, and gauge ``model.expert_tile_fill``, real rows over the
        rows the kernel computes, the worst layer, with the round's
        per-expert ``counts`` [n_moe, E] spread evenly over its steps and
        MC samples.  No device work."""
        from repro.api.models import lm_config
        from repro.models.moe import expert_matmul_path, expert_tile_fill

        inf, data = self.spec.inference, self.spec.data
        cfg = lm_config(inf.arch, inf.n_layers)
        m = (self.data.n_agents * inf.n_mc_samples * data.batch_size
             * self.data.dim * cfg.top_k)
        d, f = cfg.d_model, cfg.expert_d_ff
        reg.counter("model.expert_matmul_path", "rounds by routed-expert "
                    "grouped-matmul execution").inc(
                        path=expert_matmul_path(m, d, f))
        products = data.local_updates * inf.n_mc_samples
        fill = min(expert_tile_fill(np.round(row / products).astype(np.int64),
                                    m, d, f) for row in counts)
        reg.gauge("model.expert_tile_fill", "real rows over the rows the "
                  "grouped-matmul kernel computes, worst layer").set(fill)

    def _obs_attention(self, reg) -> None:
        """The LM's latent-attention execution, as ``mla_block`` decides it
        at trace time from the backend and the sequence length (the data's
        ``dim``): counter ``model.attention_path{path=flash|chunked}`` once
        a round, and gauge ``model.attention_block_fill``, the key blocks
        it computes over all key blocks.  No device work."""
        from repro.models.attention import mla_attention_path, mla_block_fill

        s = self.data.dim
        reg.counter("model.attention_path", "rounds by latent-attention "
                    "execution").inc(path=mla_attention_path(s))
        reg.gauge("model.attention_block_fill", "key blocks the attention "
                  "computes over all key blocks").set(mla_block_fill(s))

    def run(
        self,
        n_rounds: int | None = None,
        w_schedule=None,
        eval_fn: Callable[["Session"], dict] | None = None,
        eval_every: int | None = None,
    ) -> list[dict]:
        """Run ``n_rounds`` rounds (default: ``spec.run.n_rounds``).

        ``w_schedule`` overrides the spec topology and accepts all three
        forms — a static W, a list cycled over rounds, or a round-indexed
        ``Callable[[int], W]``.  The override is PER CALL and is not
        checkpointed: a session restored via ``Session.load`` resumes on the
        spec topology, so put a resumable schedule in the spec itself
        (``TopologySpec(kind="schedule", ...)``).  ``eval_fn(session)`` is
        merged into the history every ``eval_every`` rounds (default
        ``spec.run.eval_every``; always on the final round when enabled).
        """
        n = self.spec.run.n_rounds if n_rounds is None else n_rounds
        w_for_round = (
            as_w_schedule(w_schedule)
            if w_schedule is not None
            else self._spec_w_schedule()
        )
        eval_every = (
            self.spec.run.eval_every if eval_every is None else eval_every
        )
        history: list[dict] = []
        with _span(self._obs, "session.run", n_rounds=n):
            for i in range(n):
                rec = self.round(W=w_for_round(self.round_idx))
                if eval_every and ((i + 1) % eval_every == 0 or i == n - 1):
                    if eval_fn is not None:
                        rec.update(eval_fn(self))
                    history.append(rec)
        self.history.extend(history)
        return history

    # -- results -------------------------------------------------------------

    def posterior(self):
        """The network posterior (``FlatPosterior`` [N, P] for BbB engines,
        stacked ``FullCovGaussian`` for the conjugate linreg engine)."""
        return self.engine.posterior(self.state)

    def agent_posterior(self, agent: int):
        """One agent's posterior (leading agent axis indexed away)."""
        return jax.tree.map(lambda l: l[agent], self.posterior())

    def predictive(self, agent: int, x, n_mc: int = 8, key=None):
        """MC predictive class probabilities for one agent (paper Sec 4.2).

        ``n_mc=0`` is the deterministic point estimate: one softmax at the
        posterior MEAN (the paper's L=1 serving fast path / the non-Bayesian
        confidence baseline) — no sampling, ``key`` ignored."""
        if self.model is None or self.model.logits_fn is None:
            raise ValueError("predictive() requires a classification model")
        post = self.agent_posterior(agent)
        if n_mc == 0:
            from repro.core.flat import FlatPosterior

            mean = (post.layout.unflatten(post.mean)
                    if isinstance(post, FlatPosterior) else post.mean)
            return jax.nn.softmax(self.model.logits_fn(mean, jnp.asarray(x)), -1)
        key = jax.random.key(97) if key is None else key
        return mc_predict(
            post, self.model.logits_fn, jnp.asarray(x), key, n_mc=n_mc,
        )

    # -- serving (ROADMAP "Serving"; repro.serve) ----------------------------

    @property
    def serve_store(self):
        """The session's ``serve.SnapshotStore`` (lazy; clock = the round
        counter, so snapshot AGE is measured in training windows)."""
        if self._serve_store is None:
            from repro.serve import SnapshotStore

            self._serve_store = SnapshotStore(clock=lambda: self.round_idx)
        return self._serve_store

    def snapshot(self, dtype=None):
        """Publish the consensus posterior into the serving double buffer.

        Copies the live ``FlatPosterior`` into an immutable
        ``PosteriorSnapshot`` (optionally ``dtype="bf16"``-resident — half
        the serving HBM; default: ``spec.serve.snapshot_dtype``), stamps it
        with the current window index and the engine's gossip telemetry
        (``snapshot_meta``: staleness percentiles, quarantine counts), and
        atomically swaps it in as the served front buffer.  Pure READ of
        training state: a run with serving readers attached stays bitwise
        identical to one without (pinned by tests/test_serve.py)."""
        from repro.core.flat import FlatPosterior

        post = self.posterior()
        if not isinstance(post, FlatPosterior):
            raise ValueError(
                "Session.snapshot() serves flat BbB posteriors; the "
                f"{type(self.engine).__name__} posterior is not a "
                "FlatPosterior"
            )
        if dtype is None:
            dtype = self.spec.serve.snapshot_dtype
        meta_fn = getattr(self.engine, "snapshot_meta", None)
        telemetry = meta_fn(self.state) if meta_fn is not None else {}
        obs = self._obs
        with _span(obs, "serve.publish", window=self.round_idx, dtype=dtype):
            snap = self.serve_store.publish(
                post, window=self.round_idx, dtype=dtype, telemetry=telemetry,
            )
        if obs is not None:
            obs.registry.counter(
                "serve.published", "snapshots published"
            ).inc()
            obs.registry.gauge(
                "serve.snapshot_bytes", "front-buffer residency"
            ).set(snap.nbytes())
        return snap

    def attach_server(self, **overrides):
        """A ``serve.PredictiveServer`` bound to this session's snapshot
        store and model apply.  Defaults come from ``spec.serve``
        (``mc_samples`` / ``bucket_sizes`` / ``max_staleness`` /
        ``staleness_policy``); keyword ``overrides`` win.  The server reads
        only published snapshots — call ``snapshot()`` first (and again
        whenever the served posterior should roll forward).  The attached
        server's telemetry shows up in ``evaluate()``."""
        if self.model is None or self.model.logits_fn is None:
            raise ValueError(
                "attach_server() requires a classification model (the "
                "conjugate linreg engine has no serving path; an LM "
                "posterior would need token-level decoding through the "
                "frozen trunk, which repro.serve does not implement)"
            )
        from repro.serve import PredictiveServer

        s = self.spec.serve
        kwargs = dict(
            mc_samples=s.mc_samples,
            bucket_sizes=s.bucket_sizes,
            max_staleness=s.max_staleness,
            staleness_policy=s.staleness_policy,
        )
        kwargs.update(overrides)
        self._server = PredictiveServer(
            self.serve_store, self.model.logits_fn, **kwargs
        )
        # host-side observer hook: request spans + counters in the registry
        self._server.obs = self._obs
        return self._server

    def health(self) -> dict:
        """Per-agent posterior health probe (ROADMAP "Robustness").

        Flat BbB posteriors run the same finiteness / positivity /
        magnitude validity check the quarantine guard applies at the
        consensus exchange boundary (``core.flat.payload_validity``), so
        ``ok[i]`` is exactly "agent i's posterior would be accepted by a
        quarantined peer".  Other engines (conjugate linreg) fall back to
        an all-leaves-finite probe.  Pure read — no state is modified."""
        post = self.posterior()
        from repro.core.flat import FlatPosterior, payload_validity

        if isinstance(post, FlatPosterior):
            ok = np.asarray(payload_validity(post.mean, post.rho))
        else:
            flags = [
                np.isfinite(
                    np.asarray(leaf).reshape(np.asarray(leaf).shape[0], -1)
                ).all(axis=1)
                for leaf in jax.tree.leaves(post)
            ]
            ok = np.logical_and.reduce(flags)
        return {
            "ok": [bool(v) for v in ok],
            "n_healthy": int(ok.sum()),
            "all_ok": bool(ok.all()),
        }

    def evaluate(self, n_mc: int = 4, key=None) -> dict:
        """Held-out test metrics per agent: MC-predictive accuracy for
        classification, global-test MSE for linreg.  Engines exposing a
        ``telemetry(state)`` hook (the gossip runtime: staleness percentiles,
        merge counts, fault/quarantine counters) contribute an ``"engine"``
        block, and a serving tier (published snapshots / an attached
        ``PredictiveServer``) a ``"serving"`` block — snapshot
        age/version/bytes and SLO breach counts next to the fault and
        staleness metrics.

        Each producer owns its NAMESPACE: engine telemetry lands under
        ``out["engine"]``, never splatted into the top level — a telemetry
        key can therefore never clobber a metric key (or vice versa;
        regression-pinned by tests/test_obs.py).  With observability
        enabled every block is also ingested into the metrics registry
        under the same namespace, so the dashboard/exporter read the exact
        numbers returned here."""
        obs = self._obs
        with _span(obs, "session.evaluate", n_mc=n_mc):
            out = self._evaluate_metrics(n_mc=n_mc, key=key)
            telemetry = getattr(self.engine, "telemetry", None)
            if telemetry is not None:
                out["engine"] = telemetry(self.state)
            if self._server is not None:
                out["serving"] = self._server.telemetry()
            elif self._serve_store is not None:
                out["serving"] = self._serve_store.telemetry()
        if obs is not None:
            for ns in ("engine", "serving"):
                if ns in out:
                    obs.registry.ingest(ns, out[ns])
            for k in ("avg_acc", "avg_mse"):
                if k in out:
                    obs.registry.gauge(f"eval.{k}").set(out[k])
        return out

    def dashboard(self) -> str:
        """Compact terminal summary of the run so far: loop counters, the
        engine's staleness/merge/fault registry reads, serving state, the
        convergence verdict (measured decay rate vs the graph's theoretical
        rate), and the warm/compile span table.  Returns a printable string;
        works with observability disabled (a one-line pointer at
        ``ObsSpec``) so examples can call it unconditionally."""
        lines = [
            f"=== session dashboard · engine={self.engine.name} "
            f"round={self.round_idx} ==="
        ]
        obs = self._obs
        if obs is None:
            lines.append(
                "observability disabled — enable with "
                "ExperimentSpec(obs=ObsSpec(enabled=True))"
            )
            return "\n".join(lines)
        reg = obs.registry
        loss = reg.gauge("session.loss").value()
        n_tr = reg.gauge("session.n_trained").value()
        lines.append(
            f"rounds {int(reg.counter('session.rounds').value())}"
            f"  loss {loss:.4f}  n_trained {int(n_tr)}"
        )
        g_windows = reg.counter("gossip.windows").value()
        if g_windows:
            lines.append(
                f"gossip: windows {int(g_windows)}"
                f"  jit_traces {int(reg.gauge('gossip.jit_traces').value())}"
                f"  staleness p50/p90/max "
                f"{reg.gauge('engine.staleness.p50').value():.0f}/"
                f"{reg.gauge('engine.staleness.p90').value():.0f}/"
                f"{reg.gauge('engine.staleness.max').value():.0f}"
                f"  merges {int(reg.gauge('engine.merges.total').value())}"
            )
        published = reg.counter("serve.published").value()
        if published:
            lines.append(
                f"serving: published {int(published)}"
                f"  snapshot_bytes "
                f"{int(reg.gauge('serve.snapshot_bytes').value())}"
                f"  requests {int(reg.counter('serve.requests').value())}"
                f"  slo_breaches "
                f"{int(reg.gauge('serving.slo.breaches').value())}"
            )
        if obs.convergence is not None and obs.convergence.stats:
            rep = obs.convergence.report()
            latest = rep["latest"]
            line = (
                f"convergence: disagreement {latest['disagreement']:.3e}"
            )
            if "kl_to_mean" in latest:
                line += f"  KL(q_i||q_bar) {latest['kl_to_mean']:.3e}"
            if rep["measured_rate"] is not None:
                line += f"  measured_rate {rep['measured_rate']:.4f}"
            if rep["theory_rate"] is not None:
                line += f"  theory_rate {rep['theory_rate']:.4f}"
            if rep["rate_attainment"] is not None:
                line += f"  rate_attainment {rep['rate_attainment']:.2f}"
            lines.append(line)
        summ = obs.tracer.summary()
        for name in sorted(summ):
            for mode in ("warm", "compile"):
                if mode in summ[name]:
                    s = summ[name][mode]
                    lines.append(
                        f"span {name:<22s} {mode:<7s} n {s['n']:>4d}"
                        f"  p50 {s['p50_us']:>10.1f}us"
                        f"  max {s['max_us']:>10.1f}us"
                    )
        obs.flush()
        return "\n".join(lines)

    def _evaluate_metrics(self, n_mc: int = 4, key=None) -> dict:
        if self.data.kind == "linreg":
            phi_t, y_t = self.data.test_phi, self.data.test_y
            mean = np.asarray(self.posterior().mean)
            mses = [
                float(np.mean((phi_t @ mean[i] - y_t) ** 2))
                for i in range(self.data.n_agents)
            ]
            return {"mse": mses, "avg_mse": float(np.mean(mses))}
        if self.data.kind == "tokens":
            raise NotImplementedError(
                "Session.evaluate() on an LM posterior: the held-out token "
                "NLL needs a held-out token stream and an apply of the "
                "adapter posterior's mean through the frozen trunk, neither "
                "of which exists yet")
        key = jax.random.key(99) if key is None else key
        yt = np.asarray(self.data.y_test)
        accs = []
        for i in range(self.data.n_agents):
            probs = self.predictive(i, self.data.x_test, n_mc=n_mc, key=key)
            pred = np.asarray(jnp.argmax(probs, -1))
            accs.append(float((pred == yt).mean()))
        return {"acc": accs, "avg_acc": float(np.mean(accs))}

    # -- checkpointing -------------------------------------------------------

    def save(self, path: str) -> None:
        """Self-describing checkpoint: the spec doc + engine-state leaves +
        loop counters.  ``Session.load(path)`` needs nothing else.  Only the
        SPEC is persisted — per-call ``run(w_schedule=...)`` overrides are
        not (see ``run``); resume is bit-identical for spec-driven runs."""
        from repro.checkpoint.io import save_session

        save_session(
            path,
            self.spec.to_doc(),
            self.state,
            round_idx=self.round_idx,
            key_data=np.asarray(jax.random.key_data(self.key)),
        )

    @classmethod
    def load(cls, path: str) -> "Session":
        """Rebuild the session from an embedded spec and resume: the engine
        is reconstructed from the spec, then the saved state leaves are
        restored into its (identical) state structure."""
        from repro.checkpoint.io import restore_leaf, restore_session

        spec_doc, leaves, round_idx, key_data = restore_session(path)
        session = build_session(ExperimentSpec.from_doc(spec_doc))
        ref_leaves, treedef = jax.tree.flatten(session.state)
        if len(leaves) != len(ref_leaves):
            raise ValueError(
                f"checkpoint has {len(leaves)} state leaves, the rebuilt "
                f"engine expects {len(ref_leaves)}"
            )
        session.state = jax.tree.unflatten(
            treedef,
            [restore_leaf(s, ref) for s, ref in zip(leaves, ref_leaves)],
        )
        session.round_idx = int(round_idx)
        session.key = jax.random.wrap_key_data(jnp.asarray(np.asarray(key_data)))
        return session

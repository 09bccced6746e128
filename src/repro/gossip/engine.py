"""``GossipEngine`` — the event-driven asynchronous runtime behind
``api.Session``.

One ``run_round`` call executes one EVENT WINDOW (``gossip.clocks``) as ONE
jitted program: per-agent local Bayes-by-Backprop steps, then the masked
active-edge consensus (``core.flat.consensus_flat_masked`` — Pallas
``consensus_fused_masked`` on TPU, masked fused XLA elsewhere).  The
``Engine`` protocol is unchanged — the Session hands the engine the
window's effective W-tilde exactly as it hands the synchronous engines a
scheduled W — so specs, checkpoints, and the round loop all work
untouched.  The activity mask is the clock's host-exact ``window.active``
threaded into the jitted window as an explicit argument — it is NOT
re-derived from the float32-cast W-tilde diagonal, which would silently
drop any fired in-edge below f32 resolution (``1.0 - w`` rounds back to
exactly 1.0 for ``w < 2^-24``, misclassifying an active agent as idle and
skipping its merge — and, under ``local_policy="active"``, its training).

Four window EXECUTIONS, all the same eq.-(6) math (the equivalence
ladder pinned by tests/test_gossip.py — synchronous == instant gossip ==
sharded gossip, bitwise):

* dense masked (default, ``InferenceSpec.consensus_impl="auto"|"masked"``)
  — the whole window inside one jitted call;
* sharded ppermute (``consensus_impl="ppermute"``) — the flat [N, P]
  buffers are block-sharded over the local devices on an ``("agents",)``
  mesh and each window executes as one ``shard_map`` that ppermutes only
  the window's fired shard offsets
  (``launch.consensus_opt.consensus_ppermute_window``; the static
  per-window permutation schedule derives from ``EventWindow.edges``, so
  the local phase still traces once and each distinct window support
  compiles one cached consensus program);
* edge-native segments (``consensus_impl="segments"``, auto-chosen for
  ``kind="sparse"`` topologies driven by a clock) — the window is a
  ``gossip.clocks.SparseWindow`` (fired ``[E_w]`` dst/src/weight arrays +
  the per-agent conserve-rule self-weight vector + the explicit host-exact
  active mask; no ``[N, N]`` anywhere) executed through
  ``core.flat.consensus_flat_segments`` with the self terms folded into
  the edge list as N extra self-loop slots — on TPU the destination-major
  row-gather kernel (tables of ``clock.max_in_degree + 1`` slots a row),
  elsewhere the XLA segment sum (``core.flat.segments_mode``; the
  registry's ``gossip.consensus_path`` says which ran).  The only
  execution that runs above ``SPARSE_DENSE_GUARD`` — Watts-Strogatz /
  Barabási-Albert populations at N = 10^4+ gossip with O(E) host work and
  O(E·P) device work per window;
* delivery latency (a ``DelayedClock`` in the spec) — events merge the SRC
  POSTERIOR AS OF FIRE TIME from a bounded ``[K, N, P]`` posterior history
  ring buffer carried in ``GossipState`` (K = max_delay + 1; slot
  ``r mod K`` holds window r's post-local-step, pre-merge posterior, so a
  lag-0 event reads the current value and latency 0 reduces BITWISE to the
  instant-delivery engine).  The consensus is the event-gather
  ``core.flat.consensus_flat_delayed``; the window's static [E_max] event
  arrays ride as traced arguments, so the whole run still traces once.

Two local-step policies (``TopologySpec.clock["local_policy"]``):

* ``"all"`` (default) — every agent trains locally every window and only
  the MERGES are event-driven (the paper's time-varying model: idle agents
  keep learning on local data; ``time_varying_star_schedule`` re-expressed
  as a gossip trace reproduces the table3 runs).
* ``"active"`` — wake-on-event: agents without an incoming activation
  sleep the whole window (posterior, optimizer state and step counter all
  pass through bit-identically) — the fully asynchronous regime where
  staleness is visible in the *local* state too.

Staleness telemetry rides in the state: per-agent window index of the last
merge and total merge count; ``Session.evaluate`` surfaces the percentiles
via ``telemetry``.

Fault tolerance (ROADMAP "Robustness"): a ``"faults"`` entry in the clock
doc attaches a deterministic agent-level fault model (``gossip.faults``) —
Markov crash/recover churn (the clock filters a crashed agent's events, so
its W-tilde row collapses to ``e_i`` and its local state freezes) and
payload corruption (a corrupted agent's WIRE (prec, prec*mu) statistics
are replaced by NaN/Inf/huge garbage at the exchange boundary; resident
state intact).  ``InferenceSpec.fault_policy`` picks the defense:
``"strict"`` trusts the wire verbatim (the undefended baseline — injected
garbage propagates), ``"quarantine"`` validates every incoming
contribution (``core.flat.payload_validity``), drops invalid ones and
reassigns their row mass to self, counting drops per agent in
``GossipState.n_quarantined``.  The fault machinery is structurally gated:
with no fault model and the strict policy the pre-fault window functions
are built verbatim, and the zero-fault quarantined window is bitwise the
strict one (tests/test_faults.py).

Equivalence contract (pinned by tests/test_gossip.py): with an
``all_edges_trace`` clock every window's W-tilde equals the base W bitwise
and every agent is active, so the GossipEngine's posterior trajectory is
BIT-IDENTICAL to ``SimulatedEngine`` on the same spec — the synchronous
runtime is literally the all-edges special case of this one.
"""
from __future__ import annotations

import contextlib
import dataclasses
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.flat import (
    FlatPosterior,
    consensus_flat_delayed,
    consensus_flat_delayed_quarantined,
    consensus_flat_masked,
    consensus_flat_masked_quarantined,
    consensus_flat_segments,
    consensus_flat_segments_quarantined,
    make_flat_nll,
    segments_mode,
)
from repro.core.numerics import canonical_wire_dtype, wire_dtype_name
from repro.core.simulated import init_network, network_local_steps, with_shared
from repro.gossip.clocks import SparseClock, SparseWindow

PyTree = Any


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class GossipState:
    """Network state + per-agent gossip telemetry (all leaves agent-leading,
    checkpointed leaf-wise like every engine state).

    ``hist_mean`` / ``hist_rho`` are the delivery-latency history ring
    buffers ([K, N, P]; slot ``r mod K`` = window r's post-local-step,
    pre-merge posterior).  Instant-delivery clocks carry ``None`` — an
    EMPTY pytree subtree, so their state flattens to exactly the pre-
    latency leaf structure and old gossip checkpoints keep loading.
    ``n_quarantined`` (fault_policy="quarantine" only, else ``None`` — the
    same empty-subtree trick) counts, per agent, the incoming consensus
    contributions dropped by the exchange-boundary validity guard."""

    posterior: FlatPosterior
    opt_state: Any
    step: jax.Array  # [N] per-agent local step counter
    round: jax.Array  # scalar int32 window counter
    last_merge: jax.Array  # [N] int32 window index of last merge (-1 = never)
    n_merges: jax.Array  # [N] int32 total merges per agent
    hist_mean: Any  # [K, N, P] stale-posterior ring buffer; None if instant
    hist_rho: Any  # [K, N, P] or None
    n_quarantined: Any = None  # [N] int32 dropped contributions; None if strict


def _agent_select(active: jax.Array, new: PyTree, old: PyTree) -> PyTree:
    """Per-leaf ``where`` over agent-leading leaves (wake-on-event policy)."""

    def sel(a, b):
        mask = active.reshape((-1,) + (1,) * (a.ndim - 1))
        return jnp.where(mask, a, b)

    return jax.tree.map(sel, new, old)


def _largest_divisor_leq(n: int, cap: int) -> int:
    for s in range(min(n, cap), 0, -1):
        if n % s == 0:
            return s
    return 1


_NO_SPAN = contextlib.nullcontext()


def _span(obs, name: str, **attrs):
    """A tracer span when an ``Observability`` is attached, else a shared
    no-op context — the uninstrumented path pays one ``is None`` check."""
    return obs.tracer.span(name, **attrs) if obs is not None else _NO_SPAN


class GossipEngine:
    """Event-driven gossip runtime behind the Engine protocol.

    The per-window transition is traced ONCE (all windows share static
    shapes: [E_max] edge capacity -> fixed [N, N] W-tilde + [N] mask + the
    delayed path's [E_max] event arrays); ``n_traces`` counts retraces so
    tests can pin the one-jitted-call-per-window contract.  (The sharded
    ppermute consensus additionally compiles one cached program per
    distinct window support — see ``consensus_ppermute_window``.)
    """

    name = "gossip"
    # wake-on-event windows report NaN losses for sleeping agents;
    # Session.round aggregates NaN-safely for engines that set this
    loss_nan_is_sentinel = True
    # the Session must hand run_round the w_schedule value VERBATIM (host
    # float64 w_eff, or a SparseWindow object) — a jnp.asarray at the
    # Session boundary would round to f32 and destroy both the exact
    # active-mask lookup and the float64 schedule-identity check; the
    # engine casts to the device itself, after the host-side work
    wants_host_w = True

    def __init__(self, spec, model, n_agents: int):
        from repro.api.engines import build_optimizer, build_schedule

        inf = spec.inference
        self.n_agents = n_agents
        self.model = model
        self.opt = build_optimizer(inf.optimizer)
        self.init_sigma = inf.init_sigma
        self.shared_init = inf.shared_init
        self.consensus_mode = inf.consensus
        clock_doc = spec.topology.clock or {}
        self.local_policy = clock_doc.get("local_policy", "all")
        if self.local_policy not in ("all", "active"):
            raise ValueError(
                f"unknown gossip local_policy {self.local_policy!r}; "
                "known: all | active"
            )
        self.clock = spec.topology.gossip_clock()
        # agent-level fault model (gossip.faults), attached by build_clock
        # from the clock doc's top-level "faults" entry; None = no churn or
        # corruption.  fault_policy picks the consensus defense.
        self.faults = getattr(self.clock, "faults", None)
        self.fault_policy = inf.fault_policy
        self.quarantine = inf.fault_policy == "quarantine"
        if (self.faults is not None
                and self.faults.spec.corrupt_rate > 0.0
                and self.consensus_mode != "gaussian"):
            raise ValueError(
                "payload corruption targets the gaussian (prec, prec*mu) "
                f"exchange; consensus={self.consensus_mode!r} exchanges no "
                "such payload (drop corrupt_rate or use gaussian consensus)"
            )
        self.max_delay = int(getattr(self.clock, "max_delay", 0))
        self.hist_slots = self.max_delay + 1 if self.max_delay > 0 else 0
        if self.max_delay > 0 and self.consensus_mode == "mean_only":
            raise ValueError(
                "delivery-latency gossip implements gaussian/none consensus; "
                "mean_only (the FedAvg baseline) runs on instant delivery"
            )
        # wire precision of the consensus exchange (ROADMAP "Wire
        # precision"): "f32" is the bitwise-uncompressed default
        self.wire_dtype = inf.wire_dtype
        # resident dtype of the [K, N, P] delivery-latency history ring
        # (bf16 halves its HBM footprint; gathered rows decode to fp32)
        if inf.history_dtype is not None and not self.hist_slots:
            raise ValueError(
                "history_dtype sizes the delivery-latency posterior "
                "history ring; this clock has no delay (wrap it in "
                '{"kind": "delayed", ...} or drop history_dtype)'
            )
        self.hist_dtype = canonical_wire_dtype(inf.history_dtype)
        from repro.api.spec import SPARSE_DENSE_GUARD

        impl = inf.consensus_impl
        sparse_clock = isinstance(self.clock, SparseClock)
        if impl == "auto":
            impl = "segments" if sparse_clock else "masked"
        self.consensus_impl = impl
        self.gather_slots = self.segments_mode = None
        if impl == "segments":
            if not sparse_clock:
                raise ValueError(
                    "consensus_impl='segments' executes edge-native "
                    "SparseWindows; this topology's clock emits dense "
                    "EventWindows (use TopologySpec kind='sparse' with a "
                    "clock doc, or consensus_impl='masked')"
                )
            if self.consensus_mode == "mean_only":
                raise ValueError(
                    "consensus_impl='segments' implements gaussian/none "
                    "consensus; mean_only (the FedAvg baseline) runs on "
                    "the dense masked path"
                )
            # a window row holds at most the base graph's in-degree of
            # fired edges plus the self term: the gather tables' static
            # width, so one trace serves every window
            self.gather_slots = self.clock.max_in_degree + 1
            self.segments_mode = segments_mode(
                n_agents, self.gather_slots, self.wire_dtype
            )
        elif sparse_clock:
            # dense view of a sparse clock: legal below the guard (the
            # segments-vs-masked equivalence ladder trains on exactly this),
            # eagerly rejected above it — SparseWindow.w_eff would raise on
            # the first window anyway, but fail at build time with the fix
            if self.consensus_impl == "ppermute":
                raise ValueError(
                    "consensus_impl='ppermute' shards dense EventWindows "
                    "by their static edge schedule; a sparse clock emits "
                    "edge-native SparseWindows (use 'segments', or "
                    "'masked' below the dense guard)"
                )
            if n_agents > SPARSE_DENSE_GUARD:
                raise ValueError(
                    "consensus_impl='masked' materializes the dense "
                    f"[N, N] window view; N={n_agents} is above "
                    f"SPARSE_DENSE_GUARD={SPARSE_DENSE_GUARD} "
                    "(use consensus_impl='segments')"
                )
        # the dense masked window runs the Pallas kernels on TPU, and so do
        # the segments windows where they run the row gather; ppermute and
        # the delayed event-gather are XLA executions
        self.pallas_consensus = (
            (impl == "masked" and not self.hist_slots)
            or self.segments_mode == "pallas"
        )
        self._mesh = None
        if self.consensus_impl == "ppermute":
            if self.max_delay > 0:
                raise ValueError(
                    "consensus_impl='ppermute' implements instant delivery; "
                    "a DelayedClock runs the history-gather path (drop the "
                    "latency wrapper or use consensus_impl='masked')"
                )
            devices = jax.devices()
            shards = inf.consensus_shards
            if shards is None:
                shards = _largest_divisor_leq(n_agents, len(devices))
            if shards > len(devices):
                raise ValueError(
                    f"consensus_shards={shards} exceeds the {len(devices)} "
                    "local devices"
                )
            if n_agents % shards:
                raise ValueError(
                    f"consensus_shards={shards} must divide "
                    f"n_agents={n_agents}"
                )
            self.n_shards = shards
            self._mesh = jax.sharding.Mesh(
                np.asarray(devices[:shards]), ("agents",)
            )
        lr_schedule = build_schedule(inf.lr, inf.lr_decay)
        nll_fn = model.nll_fn
        n_mc, kl_scale = inf.n_mc_samples, inf.kl_scale
        opt = self.opt
        policy, consensus_mode = self.local_policy, self.consensus_mode
        hist_slots = self.hist_slots
        wire_dtype, hist_dtype = self.wire_dtype, self.hist_dtype
        seg_exec = dict(slots=self.gather_slots, mode=self.segments_mode)
        merge_in_jit = self.consensus_impl != "ppermute"
        quarantine = self.quarantine
        # structural gate: with no fault model and the strict policy the
        # ORIGINAL window functions are built verbatim — the fault machinery
        # adds zero ops (and zero trace changes) to existing runs
        self._guarded = guarded = self.quarantine or self.faults is not None
        self.n_traces = 0
        # host-side observability hook (repro.obs.Observability), attached
        # by build_session when ObsSpec is enabled; never touches the jitted
        # window — spans/counters record at the dispatch boundary only
        self.obs = None

        def local_phase(state: GossipState, batches, active, key, up=None):
            """Shared pre-consensus window phase: per-agent local VI steps +
            the wake-on-event policy select + staleness bookkeeping inputs.
            Identical (bitwise) across all four window executions.

            ``active`` is the clock's HOST-EXACT [N] bool mask, threaded in
            as a traced argument (``run_round._host_active``) — never
            re-derived from the float32-cast W-tilde diagonal, where a
            fired in-edge with weight < 2^-24 rounds the diagonal back to
            exactly 1.0 and silently drops the agent's merge."""
            self.n_traces += 1  # trace-time side effect: retrace telemetry
            nll = make_flat_nll(nll_fn, state.posterior.layout)
            active = active > 0
            lr = lr_schedule(state.round)
            prior = state.posterior
            # the SHARED local phase (simulated.network_local_steps): the
            # all-edges-active window is bit-identical to the synchronous
            # round because both runtimes run this exact derivation
            post, opt_state, losses, aux = network_local_steps(
                state.posterior, prior, opt, state.opt_state, nll, batches,
                key, lr, state.step, n_samples=n_mc, kl_scale=kl_scale,
            )
            u = jax.tree.leaves(batches)[0].shape[1]
            if up is not None:
                # fault-aware (guarded windows only): crashed agents freeze —
                # no local training, no merge, NaN loss ("did not train").
                # With up all-True every select is where(True, x, .), so the
                # zero-fault guarded window stays value-identical to the
                # unguarded one (the bitwise ladder in tests/test_faults.py).
                train = (active & up) if policy == "active" else up
            elif policy == "active":
                # wake-on-event: sleeping agents' local state passes through,
                # and their (discarded) phantom losses must not pollute the
                # loss telemetry — NaN marks "did not train this window"
                # (Session.round aggregates NaN-safely and reports n_trained)
                train = active
            else:
                return post, opt_state, state.step + u, active, losses, aux
            with jax.named_scope("agent_select"):
                post = _agent_select(train, post, state.posterior)
                opt_state = _agent_select(train, opt_state, state.opt_state)
                step = jnp.where(train, state.step + u, state.step)
                losses = jnp.where(train, losses, jnp.nan)
                aux = _agent_select(train, aux,
                                    jax.tree.map(jnp.zeros_like, aux))
            if up is not None:
                active = active & up
            return post, opt_state, step, active, losses, aux

        def mean_only(post, W, active):
            """The FedAvg baseline's merge: W @ mean, W @ rho on the
            merging agents."""
            act = active[:, None]
            return dataclasses.replace(
                post,
                mean=jnp.where(act, W @ post.mean, post.mean),
                rho=jnp.where(act, W @ post.rho, post.rho),
            )

        def corrupt_fill(post, corrupt, fill_mean, fill_rho):
            """The wire payloads the corrupted agents transmit this window
            (their resident state stays intact)."""
            with jax.named_scope("fault_guard"):
                c = corrupt[:, None]
                return (jnp.where(c, fill_mean[:, None], post.mean),
                        jnp.where(c, fill_rho[:, None], post.rho))

        def merged_rows(post, merged, active):
            """``merged``'s rows on the merging agents, ``post``'s
            elsewhere."""
            act = active[:, None]
            return dataclasses.replace(
                post,
                mean=jnp.where(act, merged.mean, post.mean),
                rho=jnp.where(act, merged.rho, post.rho),
            )

        def finish(state, post, opt_state, step, active):
            merged = active if consensus_mode != "none" else jnp.zeros_like(active)
            return dataclasses.replace(
                state,
                posterior=post,
                opt_state=opt_state,
                step=step,
                round=state.round + 1,
                last_merge=jnp.where(merged, state.round, state.last_merge),
                n_merges=state.n_merges + merged.astype(jnp.int32),
            )

        def window_fn(state: GossipState, batches, W, active, key):
            post, opt_state, step, active, losses, aux = local_phase(
                state, batches, active, key
            )
            with jax.named_scope("consensus"):
                if consensus_mode == "gaussian" and merge_in_jit:
                    post = consensus_flat_masked(
                        post, W, active, wire_dtype=wire_dtype
                    )
                elif consensus_mode == "mean_only":
                    post = mean_only(post, W, active)
            return finish(state, post, opt_state, step, active), losses, aux

        def window_fn_delayed(
            state: GossipState, batches, W, active, key, edges, weights, lags
        ):
            post, opt_state, step, active, losses, aux = local_phase(
                state, batches, active, key
            )
            # record this window's post-local, PRE-merge posterior in its
            # ring slot FIRST: a lag-0 event then gathers the current value,
            # which is exactly what instant delivery merges
            slot = jnp.mod(state.round, hist_slots)
            # the ring may be resident in a narrower dtype (history_dtype);
            # astype is a no-op at the fp32 default
            hist_mean = jax.lax.dynamic_update_index_in_dim(
                state.hist_mean, post.mean.astype(hist_dtype), slot, 0
            )
            hist_rho = jax.lax.dynamic_update_index_in_dim(
                state.hist_rho, post.rho.astype(hist_dtype), slot, 0
            )
            if consensus_mode == "gaussian":
                with jax.named_scope("consensus"):
                    post = consensus_flat_delayed(
                        post, W, active, edges, weights, lags,
                        hist_mean, hist_rho, state.round,
                        wire_dtype=wire_dtype,
                    )
            new_state = finish(state, post, opt_state, step, active)
            return dataclasses.replace(
                new_state, hist_mean=hist_mean, hist_rho=hist_rho
            ), losses, aux

        def window_fn_guarded(
            state: GossipState, batches, W, active, key, up, corrupt,
            fill_mean, fill_rho,
        ):
            """Fault-aware instant window.  ``up`` gates local training
            (crashed agents freeze; the clock already rewired their W-tilde
            rows to e_i), ``corrupt`` + fills replace the corrupted agents'
            WIRE payloads at the exchange boundary (resident state intact);
            ``quarantine`` swaps in the validated consensus.  All-up /
            no-corruption inputs make every extra op a value-identity, so
            the zero-fault guarded trajectory is bitwise the strict one."""
            post, opt_state, step, active, losses, aux = local_phase(
                state, batches, active, key, up
            )
            n_q = state.n_quarantined
            if consensus_mode == "gaussian" and merge_in_jit:
                mean_src, rho_src = corrupt_fill(post, corrupt, fill_mean,
                                                 fill_rho)
                with jax.named_scope("consensus"):
                    if quarantine:
                        post, valid_src = consensus_flat_masked_quarantined(
                            post, W, active,
                            mean_src=mean_src, rho_src=rho_src,
                            wire_dtype=wire_dtype,
                        )
                        n_q = n_q + (~valid_src).astype(jnp.int32)
                    else:
                        # strict: the wire buffer is trusted verbatim, so
                        # the injected garbage reaches every receiving agent
                        # (the undefended baseline); only the exchange is
                        # poisoned — non-merging agents keep their true
                        # resident state
                        merged = consensus_flat_masked(
                            dataclasses.replace(post, mean=mean_src,
                                                rho=rho_src),
                            W, active, wire_dtype=wire_dtype,
                        )
                        post = merged_rows(post, merged, active)
            elif consensus_mode == "mean_only":
                with jax.named_scope("consensus"):
                    post = mean_only(post, W, active)
            new_state = finish(state, post, opt_state, step, active)
            return (dataclasses.replace(new_state, n_quarantined=n_q),
                    losses, aux)

        def window_fn_delayed_guarded(
            state: GossipState, batches, W, active, key, edges, weights,
            lags, up, corrupt, fill_mean, fill_rho,
        ):
            """Fault-aware delayed window: corruption applies at DELIVERY
            time by source id (every event gathered FROM a corrupted agent
            this window reads garbage, whatever its fire time); the history
            ring always records the TRUE resident posterior."""
            post, opt_state, step, active, losses, aux = local_phase(
                state, batches, active, key, up
            )
            slot = jnp.mod(state.round, hist_slots)
            hist_mean = jax.lax.dynamic_update_index_in_dim(
                state.hist_mean, post.mean.astype(hist_dtype), slot, 0
            )
            hist_rho = jax.lax.dynamic_update_index_in_dim(
                state.hist_rho, post.rho.astype(hist_dtype), slot, 0
            )
            n_q = state.n_quarantined
            if consensus_mode == "gaussian" and quarantine:
                with jax.named_scope("consensus"):
                    post, valid_e = consensus_flat_delayed_quarantined(
                        post, W, active, edges, weights, lags,
                        hist_mean, hist_rho, state.round,
                        corrupt=corrupt, fill_mean=fill_mean,
                        fill_rho=fill_rho, wire_dtype=wire_dtype,
                    )
                # count only REAL dropped events — [E_max] padding rows
                # carry zero weight and must not inflate the telemetry
                bad = ((~valid_e) & (weights > 0.0)).astype(jnp.int32)
                n_q = n_q.at[edges[:, 0]].add(bad)
            elif consensus_mode == "gaussian":
                # strict: poison the gathered copies (by src id, every
                # ring slot) — the state's ring keeps the true values
                with jax.named_scope("fault_guard"):
                    c = corrupt[None, :, None]
                    hm = jnp.where(
                        c, fill_mean.astype(hist_mean.dtype)[None, :, None],
                        hist_mean,
                    )
                    hr = jnp.where(
                        c, fill_rho.astype(hist_rho.dtype)[None, :, None],
                        hist_rho,
                    )
                with jax.named_scope("consensus"):
                    post = consensus_flat_delayed(
                        post, W, active, edges, weights, lags,
                        hm, hr, state.round, wire_dtype=wire_dtype,
                    )
            new_state = finish(state, post, opt_state, step, active)
            return dataclasses.replace(
                new_state, hist_mean=hist_mean, hist_rho=hist_rho,
                n_quarantined=n_q,
            ), losses, aux

        def _self_loops(dst, src, w_e, w_self):
            """Fold the conserve-rule self terms into the edge list as N
            trailing self-loop slots — ``consensus_flat_segments``' contract
            is that self-loops ride IN the [E] arrays."""
            ar = jnp.arange(w_self.shape[0], dtype=dst.dtype)
            return (jnp.concatenate([dst, ar]), jnp.concatenate([src, ar]),
                    jnp.concatenate([w_e, w_self]))

        def window_fn_segments(
            state: GossipState, batches, dst, src, w_e, w_self, active, key
        ):
            """Edge-native window: [E_max] fired dst/src/weight arrays +
            [N] self-weights + the host-exact active mask ride as traced
            arguments (static shapes — one trace for the whole run); no
            [N, N] is ever materialized, host or device."""
            post, opt_state, step, active, losses, aux = local_phase(
                state, batches, active, key
            )
            if consensus_mode == "gaussian":
                with jax.named_scope("consensus"):
                    d_all, s_all, w_all = _self_loops(dst, src, w_e, w_self)
                    post = consensus_flat_segments(
                        post, d_all, s_all, w_all,
                        active=active, wire_dtype=wire_dtype, **seg_exec,
                    )
            return finish(state, post, opt_state, step, active), losses, aux

        def window_fn_segments_guarded(
            state: GossipState, batches, dst, src, w_e, w_self, active,
            key, up, corrupt, fill_mean, fill_rho,
        ):
            """Fault-aware edge-native window.  The clock already filtered
            crashed agents' fired edges (``faults.edge_keep_mask``), so
            ``up`` only gates local training; quarantine validates every
            fired edge's wire payload and moves dropped in-edge mass to the
            dst's self term.  All-up / no-corruption inputs reduce to the
            unguarded call bitwise (the same equivalence-ladder rung the
            dense guarded windows pin)."""
            post, opt_state, step, active, losses, aux = local_phase(
                state, batches, active, key, up
            )
            n_q = state.n_quarantined
            if consensus_mode == "gaussian":
                mean_src, rho_src = corrupt_fill(post, corrupt, fill_mean,
                                                 fill_rho)
            if consensus_mode == "gaussian" and quarantine:
                with jax.named_scope("consensus"):
                    post, valid_e = consensus_flat_segments_quarantined(
                        post, dst, src, w_e, w_self, active=active,
                        mean_src=mean_src, rho_src=rho_src,
                        wire_dtype=wire_dtype, **seg_exec,
                    )
                # count only REAL dropped edges — [E_max] padding slots
                # carry zero weight and must not inflate the telemetry
                bad = ((~valid_e) & (w_e > 0.0)).astype(jnp.int32)
                n_q = n_q.at[dst].add(bad)
            elif consensus_mode == "gaussian":
                # strict: the wire is trusted verbatim — the corrupted
                # sources' garbage reaches every receiving agent
                with jax.named_scope("consensus"):
                    d_all, s_all, w_all = _self_loops(dst, src, w_e, w_self)
                    merged = consensus_flat_segments(
                        dataclasses.replace(post, mean=mean_src, rho=rho_src),
                        d_all, s_all, w_all,
                        active=active, wire_dtype=wire_dtype, **seg_exec,
                    )
                    post = merged_rows(post, merged, active)
            new_state = finish(state, post, opt_state, step, active)
            return (dataclasses.replace(new_state, n_quarantined=n_q),
                    losses, aux)

        if self.consensus_impl == "segments":
            fn = window_fn_segments_guarded if guarded else window_fn_segments
        elif guarded:
            fn = window_fn_delayed_guarded if self.hist_slots else window_fn_guarded
        else:
            fn = window_fn_delayed if self.hist_slots else window_fn
        self._window = jax.jit(fn) if spec.run.jit else fn

    # -- Engine protocol -----------------------------------------------------

    def init(self, key: jax.Array) -> GossipState:
        ns = init_network(
            key,
            self.n_agents,
            self.model.init_fn,
            self.opt,
            init_sigma=self.init_sigma,
            shared_init=self.shared_init,
        )
        hist_shape = (self.hist_slots,) + tuple(ns.posterior.mean.shape)
        return GossipState(
            posterior=ns.posterior,
            opt_state=ns.opt_state,
            step=ns.step,
            round=ns.round,
            last_merge=jnp.full((self.n_agents,), -1, jnp.int32),
            n_merges=jnp.zeros((self.n_agents,), jnp.int32),
            # zero-init is safe — never read before their window is written
            # (window r only gathers slots of windows >= max(0, r -
            # max_delay)); None (empty subtree) when there is no latency so
            # the leaf structure matches pre-latency gossip checkpoints.
            # Resident dtype is history_dtype (fp32 default; bf16 halves
            # the ring's HBM footprint).
            hist_mean=(jnp.zeros(hist_shape, self.hist_dtype)
                       if self.hist_slots else None),
            hist_rho=(jnp.zeros(hist_shape, self.hist_dtype)
                      if self.hist_slots else None),
            # None (empty subtree) under fault_policy="strict" so strict
            # states keep the exact pre-fault leaf structure
            n_quarantined=(jnp.zeros((self.n_agents,), jnp.int32)
                           if self.quarantine else None),
        )

    def _window_for(self, state, W):
        """The engine-side EventWindow for this round — the delayed and
        sharded paths need the static event/edge structure, which the
        Session's W-tilde alone does not carry.  Regenerated from the spec
        clock (windows are pure functions of (seed, round), so this matches
        the Session's stream bitwise — verified here), which also means
        per-round ``W`` overrides cannot be used with these paths."""
        r = int(state.round)
        win = self.clock.window(r)
        # compare in float64 — both sides' native precision.  An f32
        # comparison would false-accept any foreign schedule that merely
        # COLLIDES with the stream at f32 (e.g. weights differing by less
        # than one f32 ulp) and then silently merge with the stream's
        # event structure instead of the caller's.
        if not np.array_equal(
            np.asarray(W, np.float64), np.asarray(win.w_eff, np.float64)
        ):
            raise ValueError(
                "delayed/sharded gossip windows come from the spec clock; "
                f"the W passed for window {r} does not match its stream "
                "(per-round w_schedule overrides are unsupported on these "
                "paths)"
            )
        return win

    def _fault_arrays(self, r: int):
        """Host-side per-window fault draws (pure functions of (seed, r) —
        a resumed session regenerates the identical stream).  Also records
        ``last_crashed`` for ``Session.round``'s n_crashed telemetry."""
        n = self.n_agents
        if self.faults is None:
            up = np.ones(n, dtype=bool)
            corrupt = np.zeros(n, dtype=bool)
            fm = np.zeros(n, np.float32)
            fr = np.zeros(n, np.float32)
        else:
            up = self.faults.up(r)
            corrupt = self.faults.corrupted(r)
            fm, fr = self.faults.fills(r)
        self.last_crashed = ~up
        return (jnp.asarray(up), jnp.asarray(corrupt),
                jnp.asarray(fm), jnp.asarray(fr))

    def _host_active(self, r: int, W, win=None):
        """The HOST-EXACT [N] activity mask for window ``r`` (the headline
        mask fix): when ``W`` is the spec clock's own w_eff (the Session
        passes it verbatim — ``wants_host_w``), thread the clock's
        ``window.active`` through; only a FOREIGN per-round W override (or
        a direct ``run_round`` call with a device array) falls back to the
        diagonal derivation — computed in float64, never on the f32 cast."""
        w64 = np.asarray(W, np.float64)
        if win is None and isinstance(W, np.ndarray) \
                and W.dtype == np.float64:
            # only consult the clock for host float64 W — what the Session
            # hands over verbatim; device arrays are foreign by definition
            win = self.clock.window(r)
        if (win is not None and not isinstance(win, SparseWindow)
                and np.array_equal(w64, np.asarray(win.w_eff, np.float64))):
            return np.asarray(win.active)
        return np.diagonal(w64) < 1.0

    def _segments_round(self, state, batches, W, key, obs, r):
        """Edge-native window execution: no [N, N] is built on the host or
        traced on the device — the fired [E_max] arrays, [N] self-weights
        and [N] active mask are the whole exchange structure."""
        if not isinstance(W, SparseWindow):
            raise ValueError(
                "consensus_impl='segments' executes the spec clock's "
                "SparseWindow stream; run_round received an array-like W "
                "(per-round dense w_schedule overrides are unsupported — "
                "the Session's w_schedule yields the windows verbatim)"
            )
        if int(W.index) != r:
            raise ValueError(
                f"SparseWindow index {int(W.index)} does not match the "
                f"engine round {r} (windows are pure functions of "
                "(seed, round); the stream must be consumed in order)"
            )
        with _span(obs, "gossip.window_build", round=r):
            extra = self._fault_arrays(r) if self._guarded else ()
            args = (
                jnp.asarray(W.dst), jnp.asarray(W.src),
                jnp.asarray(W.weights),
                jnp.asarray(W.self_weight, dtype=jnp.float32),
                jnp.asarray(W.active),
            )
        with _span(obs, "gossip.window", impl="segments", round=r):
            out = self._window(state, batches, *args, key, *extra)
        self._obs_after_window(obs, W)
        return out

    def run_round(self, state, batches, W, key):
        """One window; the nll's aux (the model's counters per agent) is
        kept as ``last_aux`` for ``Session.round``'s telemetry."""
        state, losses, self.last_aux = self._run_window(
            state, with_shared(batches, self.model.shared), W, key)
        return state, losses

    def _run_window(self, state, batches, W, key):
        obs = self.obs
        r = int(state.round)
        if self.consensus_impl == "segments":
            return self._segments_round(state, batches, W, key, obs, r)
        spec_win = None
        if isinstance(W, SparseWindow):
            # dense view of an edge-native window (below the guard only) —
            # the segments-vs-masked equivalence ladder runs on this
            spec_win, W = W, W.w_eff
        ppermute = (self.consensus_impl == "ppermute"
                    and self.consensus_mode == "gaussian")
        with _span(obs, "gossip.window_build", round=r):
            extra = self._fault_arrays(r) if self._guarded else ()
            win = (self._window_for(state, W)
                   if (self.hist_slots or ppermute) else None)
            active = (np.asarray(spec_win.active) if spec_win is not None
                      else self._host_active(r, W, win))
            W = jnp.asarray(W)
            act = jnp.asarray(active)
        if self.hist_slots:
            # ONE fused jitted call: local phase + event-gather consensus
            # (dispatch-side wall clock; Session.round owns the synced span)
            with _span(obs, "gossip.window", impl="delayed", round=r):
                out = self._window(
                    state, batches, W, act, key,
                    jnp.asarray(win.edges), jnp.asarray(win.weights),
                    jnp.asarray(win.delays), *extra,
                )
            self._obs_after_window(obs)
            return out
        if ppermute:
            with _span(obs, "gossip.local_phase", impl="ppermute", round=r):
                state, losses, aux = self._window(
                    state, batches, W, act, key, *extra
                )
            with _span(obs, "gossip.consensus", impl="ppermute", round=r):
                state = self._ppermute_consensus(state, W, win, extra)
            self._obs_after_window(obs)
            return state, losses, aux
        # dense masked path: local phase + consensus fused in one call
        with _span(obs, "gossip.window", impl="masked", round=r):
            out = self._window(state, batches, W, act, key, *extra)
        self._obs_after_window(obs)
        return out

    def _obs_after_window(self, obs, win=None) -> None:
        """Registry bookkeeping after one window (host-side, pure observer).
        ``win`` is the edge-native window a segments execution just ran."""
        if obs is None:
            return
        obs.registry.counter(
            "gossip.windows", "event windows executed"
        ).inc()
        obs.registry.gauge(
            "gossip.jit_traces", "distinct window traces (retrace telemetry)"
        ).set(self.n_traces)
        if win is None or self.consensus_mode != "gaussian":
            return
        gather = self.segments_mode != "xla"
        obs.registry.counter(
            "gossip.consensus_path",
            "edge-native windows by consensus execution",
        ).inc(path="row_gather" if gather else "segment_sum")
        if gather:
            # real table entries (fired edges and the merging rows' self
            # terms) over the N x D slots the kernel's grid walks
            real = win.n_events + int(np.count_nonzero(win.active))
            obs.registry.gauge(
                "gossip.gather_slot_fill",
                "real entries of the row-gather tables over N x D",
            ).set(real / (self.n_agents * self.gather_slots))

    def _ppermute_consensus(self, state, W, win, extra):
        """The host-level sharded consensus dispatch (the one window
        execution whose consensus is a separate program from the local
        phase — which is why it gets its own span in ``run_round``)."""
        post = state.posterior
        if not self._guarded:
            post = consensus_flat_masked(
                post, W, jnp.asarray(win.active),
                mode="ppermute", mesh=self._mesh, axis="agents",
                window=win, wire_dtype=self.wire_dtype,
            )
            return dataclasses.replace(state, posterior=post)
        up, corrupt, fm, fr = extra
        c = corrupt[:, None]
        mean_src = jnp.where(c, fm[:, None], post.mean)
        rho_src = jnp.where(c, fr[:, None], post.rho)
        active = jnp.asarray(win.active)
        if self.quarantine:
            post, valid_src = consensus_flat_masked_quarantined(
                post, W, active, mean_src=mean_src, rho_src=rho_src,
                mode="ppermute", mesh=self._mesh, axis="agents",
                window=win, wire_dtype=self.wire_dtype,
            )
            state = dataclasses.replace(
                state, posterior=post,
                n_quarantined=(state.n_quarantined
                               + (~valid_src).astype(jnp.int32)),
            )
        else:
            merged = consensus_flat_masked(
                dataclasses.replace(post, mean=mean_src, rho=rho_src),
                W, active, mode="ppermute", mesh=self._mesh,
                axis="agents", window=win, wire_dtype=self.wire_dtype,
            )
            act = active[:, None]
            post = dataclasses.replace(
                post,
                mean=jnp.where(act, merged.mean, post.mean),
                rho=jnp.where(act, merged.rho, post.rho),
            )
            state = dataclasses.replace(state, posterior=post)
        return state

    def posterior(self, state) -> FlatPosterior:
        return state.posterior

    # -- telemetry -----------------------------------------------------------

    def staleness(self, state) -> np.ndarray:
        """[N] windows since each agent's last merge (never merged = age of
        the whole run) — the per-agent posterior age the async analyses
        (BayGo; Lalitha et al. 2019) bound."""
        n = int(state.round)
        last = np.asarray(state.last_merge)
        return np.where(last >= 0, (n - 1) - last, n).astype(np.int64)

    def telemetry(self, state) -> dict:
        """Merged into ``Session.evaluate`` output: staleness percentiles +
        merge counts over the run so far (plus the delivery-latency depth
        and shard count when those paths are active)."""
        age = self.staleness(state)
        merges = np.asarray(state.n_merges)
        out = {
            "staleness": {
                "p50": float(np.percentile(age, 50)),
                "p90": float(np.percentile(age, 90)),
                "max": int(age.max()),
                "mean": float(age.mean()),
            },
            "merges": {
                "per_agent_mean": float(merges.mean()),
                "min": int(merges.min()),
                "total": int(merges.sum()),
            },
            "windows": int(state.round),
        }
        if self.max_delay:
            out["max_delay"] = self.max_delay
        if self._mesh is not None:
            out["consensus_shards"] = self.n_shards
        if self.wire_dtype != "f32":
            out["wire_dtype"] = self.wire_dtype
        if self.hist_slots and wire_dtype_name(self.hist_dtype) != "f32":
            out["history_dtype"] = wire_dtype_name(self.hist_dtype)
        if self._guarded:
            nw = int(state.round)
            faults: dict = {"policy": self.fault_policy}
            if self.faults is not None:
                uptime = self.faults.uptime(nw)
                faults["uptime"] = {
                    "per_agent": [int(v) for v in uptime],
                    "frac_mean": (float(uptime.mean()) / nw if nw else 1.0),
                    "min": int(uptime.min()) if nw else 0,
                }
                faults["currently_down"] = (
                    int(self.faults.crashed(nw - 1).sum()) if nw else 0
                )
            if getattr(state, "n_quarantined", None) is not None:
                nq = np.asarray(state.n_quarantined)
                faults["quarantined"] = {
                    "per_agent": [int(v) for v in nq],
                    "total": int(nq.sum()),
                }
            out["faults"] = faults
        return out

    def snapshot_meta(self, state) -> dict:
        """The gossip provenance a serving snapshot carries (ROADMAP
        "Serving"): the window index, staleness percentiles, merge counts
        and quarantine totals AT PUBLISH TIME — the raw material of the
        serving tier's bounded-staleness SLO
        (``serve.PredictiveServer(max_staleness=k)``).  Plain data,
        checkpoint-embeddable next to the snapshot buffers."""
        age = self.staleness(state)
        merges = np.asarray(state.n_merges)
        meta = {
            "window": int(state.round),
            "staleness": {
                "p50": float(np.percentile(age, 50)),
                "p90": float(np.percentile(age, 90)),
                "max": int(age.max()),
            },
            "merges_total": int(merges.sum()),
        }
        if getattr(state, "n_quarantined", None) is not None:
            meta["quarantined_total"] = int(
                np.asarray(state.n_quarantined).sum()
            )
        return meta

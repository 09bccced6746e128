"""Chip smoke test: the system's main path once, on one TPU, at the paper
MLP's width.

Everything runs in this one process through the user entry points of
``repro.api`` (``build_session`` -> ``Session.run`` -> engine -> consensus
-> ``Session.snapshot`` -> ``PredictiveServer``), with the 784-200-200-10
MLP (P = 199,210 posterior parameters per agent) trained on ``mnist_like``
data (784 features, 10 classes, 6,000 train and 1,000 test rows per class),
random weights from a seed:

  (a) synchronous session, N = 256 agents on a 16x16 torus: 3 rounds and
      one ``evaluate()`` (Pallas ``consensus_fused_network``);
  (e) one consensus call on (a)'s posterior checked against the XLA
      ``consensus_flat_reference`` at fp32 tolerance, and the lowered
      consensus program shown to hold ``tpu_custom_call``;
  (d) ``snapshot()`` + ``attach_server()`` on (a): 32 requests at
      ``mc_samples=8``, plus a point estimate checked against
      ``Session.predictive``;
  (b) Poisson gossip on the same torus under ``fault_policy="quarantine"``
      with a small crash/corruption fault model: 3 windows (Pallas
      ``consensus_fused_masked`` + ``payload_validity_fused``);
  (c) edge-native Watts-Strogatz Poisson gossip, N = 512 agents: 3 windows
      (``consensus_impl="segments"``).  N = 1024 does not fit: compiled for
      a v5e, its window program needs 17.9 GiB (4.75 arguments, 4.56
      outputs, 8.61 temporaries) of the chip's 16 GB.

``--four-chips`` runs only the sharded gossip window instead: the (b)
torus without faults under ``consensus_impl="ppermute"`` (agent axis over
four TPU devices) against the same spec under ``"masked"``, f32 and bf16
wire, and checks their largest difference against 1e-4 plus, for a
narrower wire, 4 * rounds * (its unit roundoff) * the posterior's scale:
either path may round a contribution to the neighbouring wire value in
every window.  Those runs use ``lr=0`` and
per-agent initializations, so the posteriors move by consensus alone: with
training on, Adam's ``m / sqrt(v)`` turns last-bit gradient differences
between the sharded and the one-device local phase into differences of
up to 2 * lr per parameter, which would hide the consensus.

Usage (from the checkout root; ``src/`` is put on the path here):

  python chip_smoke.py               # one chip, phases (a)-(e)
  python chip_smoke.py --four-chips  # four chips, sharded window only

Each phase prints its compile and warm seconds and the device's
``peak_bytes_in_use``.  The last line of stdout is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}``;
it is printed only when JAX's first device is a TPU and every phase passed,
otherwise the script exits non-zero.
"""
from __future__ import annotations

import argparse
import gc
import json
import statistics
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.api import (  # noqa: E402
    DataSpec,
    ExperimentSpec,
    InferenceSpec,
    RunSpec,
    TopologySpec,
    build_session,
)
from repro.core.flat import (  # noqa: E402
    FlatPosterior,
    consensus_flat,
    consensus_flat_masked_quarantined,
    consensus_flat_reference,
)
from repro.core.numerics import wire_error_bound  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402

P_MLP = 784 * 200 + 200 + 200 * 200 + 200 + 200 * 10 + 10  # 199,210
TORUS = (16, 16)
N_SPARSE = 512  # 1024 needs 17.9 GiB per window program (module docstring)
ROUNDS = 3
FAULTS = {"crash_rate": 0.05, "recover_rate": 0.5, "corrupt_rate": 0.05,
          "corrupt_kind": "mix", "seed": 0}


class SmokeFailure(RuntimeError):
    """A phase produced a wrong or missing result."""


def _require(ok, what) -> None:
    # a check that survives ``python -O`` (which strips ``assert``)
    if not ok:
        raise SmokeFailure(str(what))


def _spec(topology: TopologySpec, n_agents: int,
          **inference) -> ExperimentSpec:
    return ExperimentSpec(
        topology=topology,
        data=DataSpec(
            dataset="mnist_like",
            dataset_params=dict(dim=784, n_classes=10,
                                n_train_per_class=6000,
                                n_test_per_class=1000),
            partition="iid",
            partition_params=dict(n_agents=n_agents),
        ),
        inference=InferenceSpec(hidden=200, depth=2, **inference),
        run=RunSpec(n_rounds=ROUNDS, seed=0),
    )


def sync_spec() -> ExperimentSpec:
    rows, cols = TORUS
    topo = TopologySpec(kind="torus", params={"rows": rows, "cols": cols})
    return _spec(topo, rows * cols)


def gossip_spec(faults: dict | None = FAULTS, **inference) -> ExperimentSpec:
    rows, cols = TORUS
    clock = {"kind": "poisson", "rate": 0.5, "seed": 0}
    if faults:
        clock["faults"] = dict(faults)
        inference.setdefault("fault_policy", "quarantine")
    topo = TopologySpec.gossip("torus", {"rows": rows, "cols": cols}, clock)
    return _spec(topo, rows * cols, **inference)


def sparse_spec() -> ExperimentSpec:
    topo = TopologySpec.sparse(
        "watts_strogatz", n=N_SPARSE, k=6, beta=0.1, seed=0,
        clock={"kind": "poisson", "rate": 0.5, "seed": 0},
    )
    return _spec(topo, N_SPARSE)


def _peak_bytes() -> int:
    stats = jax.devices()[0].memory_stats() or {}
    return int(stats.get("peak_bytes_in_use", -1))


def _finite(x) -> bool:
    return bool(jnp.isfinite(x).all())


def _check_posterior(post, n_agents: int) -> None:
    _require(post.mean.shape == (n_agents, P_MLP), post.mean.shape)
    _require(post.rho.shape == (n_agents, P_MLP), post.rho.shape)
    _require(_finite(post.mean) and _finite(post.rho),
             "non-finite posterior")


def _run_rounds(name: str, session, n_agents: int) -> None:
    """``ROUNDS`` rounds; the first one's seconds include its compile."""
    secs, losses = [], []
    for _ in range(ROUNDS):
        t0 = time.perf_counter()
        rec = session.round()
        secs.append(time.perf_counter() - t0)
        losses.append(rec["loss"])
    _require(all(l is not None and np.isfinite(l) for l in losses), losses)
    _check_posterior(session.posterior(), n_agents)
    _report(name, n_agents=n_agents, compile_s=secs[0],
            warm_s=statistics.median(secs[1:]), losses=losses)


def _report(name: str, **fields) -> None:
    print(json.dumps({"phase": name, **fields,
                      "peak_bytes_in_use": _peak_bytes()}), flush=True)


def phase_sync():
    n = TORUS[0] * TORUS[1]
    session = build_session(sync_spec())
    _run_rounds("a_sync_rounds", session, n)
    t0 = time.perf_counter()
    ev = session.evaluate()
    t_eval = time.perf_counter() - t0
    _require(len(ev["acc"]) == n and np.isfinite(ev["avg_acc"]),
             ev["avg_acc"])
    _report("a_evaluate", first_s=t_eval, avg_acc=ev["avg_acc"])
    return session


def phase_consensus_check(session) -> None:
    post = session.posterior()
    W = jnp.asarray(session.spec.topology.w_schedule()(0), jnp.float32)

    def kernel(mean, rho):
        out = consensus_flat(FlatPosterior(mean=mean, rho=rho,
                                           layout=post.layout), W)
        return out.mean, out.rho

    def guarded(mean, rho):
        posts = FlatPosterior(mean=mean, rho=rho, layout=post.layout)
        out, _ = consensus_flat_masked_quarantined(
            posts, W, jnp.ones((W.shape[0],), bool))
        return out.mean, out.rho

    kernel_calls = {
        name: jax.jit(fn).lower(post.mean, post.rho).as_text().count(
            "tpu_custom_call")
        for name, fn in (("network", kernel), ("masked_quarantined", guarded))
    }
    # network: one fused kernel; masked_quarantined: the masked kernel and
    # the validity probe (sent and resident payloads)
    _require(kernel_calls["network"] >= 1, kernel_calls)
    _require(kernel_calls["masked_quarantined"] >= 2, kernel_calls)
    got = jax.jit(kernel)(post.mean, post.rho)
    ref = jax.jit(lambda m, r: consensus_flat_reference(m, r, W))(
        post.mean, post.rho)
    errs = {}
    for name, g, r in zip(("mean", "rho"), got, ref):
        g, r = np.asarray(g), np.asarray(r)
        _require(np.isfinite(g).all(), f"non-finite consensus {name}")
        np.testing.assert_allclose(g, r, rtol=1e-5, atol=1e-6, err_msg=name)
        errs[f"max_abs_err_{name}"] = float(np.abs(g - r).max())
    _report("e_consensus_vs_reference", tpu_custom_calls=kernel_calls, **errs)


def phase_serve(session) -> None:
    t0 = time.perf_counter()
    snap = session.snapshot()
    t_pub = time.perf_counter() - t0
    server = session.attach_server(mc_samples=8)
    rng = np.random.default_rng(0)
    x_test = np.asarray(session.data.x_test)
    n_agents = session.data.n_agents
    secs = []
    for i, size in enumerate(rng.integers(1, 9, size=32)):
        rows = x_test[rng.integers(0, x_test.shape[0], size=int(size))]
        t0 = time.perf_counter()
        probs, _ = server.query(rows, agent=(i * 37) % n_agents)
        probs = np.asarray(probs)
        secs.append(time.perf_counter() - t0)
        _require(probs.shape == (size, 10) and np.isfinite(probs).all(),
                 f"request {i}: probabilities of shape {probs.shape}")
        np.testing.assert_allclose(probs.sum(-1), 1.0, atol=1e-5)
    rows = x_test[:16]
    point, _ = server.query(rows, agent=5, mc_samples=0)
    np.testing.assert_allclose(
        np.asarray(point), np.asarray(session.predictive(5, rows, n_mc=0)),
        rtol=1e-5, atol=1e-6,
    )
    lat = server.latency_percentiles()
    _report("d_serve", publish_s=t_pub, snapshot_bytes=snap.nbytes(),
            requests=server.n_requests, traces=server.n_traces,
            first_request_s=secs[0],
            warm_request_s=statistics.median(secs[1:]),
            p50_us=lat["p50_us"], p99_us=lat["p99_us"])


def phase_gossip() -> None:
    n = TORUS[0] * TORUS[1]
    session = build_session(gossip_spec())
    _run_rounds("b_gossip_quarantine", session, n)
    health = session.health()
    _require(health["all_ok"], health["n_healthy"])
    faults = session.engine.telemetry(session.state)["faults"]
    _report("b_gossip_faults", quarantined=faults["quarantined"]["total"],
            currently_down=faults["currently_down"])


def phase_sparse() -> None:
    session = build_session(sparse_spec())
    _require(session.engine.consensus_impl == "segments",
             session.engine.consensus_impl)
    _run_rounds("c_sparse_segments", session, N_SPARSE)


def four_chips() -> None:
    _require(len(jax.devices()) == 4, jax.devices())
    n = TORUS[0] * TORUS[1]
    for wire in ("f32", "bf16"):
        runs = {}
        for impl in ("ppermute", "masked"):
            session = build_session(gossip_spec(
                faults=None, consensus_impl=impl, wire_dtype=wire,
                lr=0.0, shared_init=False))
            _run_rounds(f"four_chips_{impl}_{wire}", session, n)
            runs[impl] = session
        engine = runs["ppermute"].engine
        mesh_ids = {d.id for d in engine._mesh.devices.flat}
        _require(engine.n_shards == 4 and len(mesh_ids) == 4, mesh_ids)
        a, b = runs["ppermute"].posterior(), runs["masked"].posterior()
        u = wire_error_bound(wire)
        fields = {}
        for k in ("mean", "rho"):
            x, y = getattr(a, k), getattr(b, k)
            diff = float(jnp.abs(x - y).max())
            # f32: the same math in another reduction order.  A narrower
            # wire may round one contribution to the neighbouring wire value
            # in either path, in every window (u: the wire's unit roundoff).
            bound = 1e-4 + 4 * ROUNDS * u * float(jnp.abs(y).max())
            _require(diff <= bound, f"{wire} {k}: {diff} > {bound}")
            fields.update({f"max_abs_diff_{k}": diff, f"bound_{k}": bound})
        _report(f"four_chips_ppermute_vs_masked_{wire}",
                n_shards=engine.n_shards, mesh_devices=sorted(mesh_ids),
                **fields)
        del runs, engine, a, b


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the sharded gossip window on four chips")
    args = ap.parse_args()
    dev = jax.devices()[0]
    if any(d.platform != "tpu" for d in jax.devices()):
        print(f"chip_smoke: JAX found no TPU (devices: {jax.devices()}); "
              "nothing was run", file=sys.stderr)
        sys.exit(2)
    print(json.dumps({"compile_cache": enable_compile_cache(),
                      "jax": jax.__version__}), flush=True)
    if args.four_chips:
        four_chips()
    else:
        session = phase_sync()
        phase_consensus_check(session)
        phase_serve(session)
        del session  # the serving store's clock refers back to the session
        gc.collect()
        phase_gossip()
        phase_sparse()
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))


if __name__ == "__main__":
    main()

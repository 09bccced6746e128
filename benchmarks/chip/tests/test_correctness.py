"""The comparison that decides ``correct``, at a size a CPU test can hold:
the program as the configuration states it passes; the control (one
precision step down) and each fault a cell can have fail; what no program
path or no reference reads is refused."""
import copy
import dataclasses

import jax
import jax.numpy as jnp
import pytest

import tiny
import run
from chipbench import cells, correct

TRAIN = [w["name"] for w in cells.load_benchmark()["workloads"]]
SEED = 2_147_483_711


def run_tiny(name, seed=SEED):
    return run.run_cell(name, seed, 1.0, False, cell=tiny.tiny_cell(name),
                        devices=jax.devices(), say=lambda *_: None)


@pytest.mark.parametrize("name", TRAIN)
def test_program_is_correct(name):
    res = run_tiny(name)
    assert res["correct"], res["checks"]
    assert res["failed"] == 0 and res["attempted"] > 0
    assert list(res)[-1] == "checks"


@pytest.mark.parametrize("name", TRAIN)
def test_train_control_fails(name):
    """The reference in bfloat16, in the program's place."""
    cell = tiny.tiny_cell(name)
    ref = cells.reference_module(cell.config)
    r32 = ref.train_readings(cell.config, cell.traffic, SEED, 3)
    r16 = ref.train_readings(cell.config, cell.traffic, SEED, 3,
                             dtype=jnp.bfloat16)
    ok, checks = correct.verdict(correct.train_numbers(r16, r32),
                                 cell.limits)
    assert not ok, checks


def _frozen_state(monkeypatch):
    from repro.api.engines import SimulatedEngine
    from repro.gossip.engine import GossipEngine

    for cls in (SimulatedEngine, GossipEngine):
        orig = cls.run_round

        def frozen(self, state, batches, W, key, _orig=orig):
            # the posterior and optimizer state come back unchanged; the
            # round counters advance, so the run goes on
            new, losses = _orig(self, state, batches, W, key)
            return dataclasses.replace(new, posterior=state.posterior,
                                       opt_state=state.opt_state), losses

        monkeypatch.setattr(cls, "run_round", frozen)


def _half_batch(monkeypatch):
    import repro.api.data as data_mod

    orig = data_mod.make_round_batches

    def halved(data, batch_size, u):
        sampler = orig(data, batch_size, u)
        h = batch_size // 2

        def sample(key, r):
            return {k: jnp.concatenate([v[:, :, :h], v[:, :, :h]], axis=2)
                    for k, v in sampler(key, r).items()}

        return sample

    monkeypatch.setattr(data_mod, "make_round_batches", halved)


def _no_exchange(monkeypatch):
    import repro.core.simulated as sim
    import repro.gossip.engine as eng

    monkeypatch.setattr(sim, "consensus_all_agents", lambda post, *a, **k: post)
    monkeypatch.setattr(eng, "consensus_flat_segments",
                        lambda post, *a, **k: post)
    monkeypatch.setattr(
        eng, "consensus_flat_masked_quarantined",
        lambda post, *a, **k: (post, jnp.ones(post.mean.shape[0], bool)))


TRAIN_FAULTS = {"state_unchanged": _frozen_state, "half_batch": _half_batch,
                "no_exchange": _no_exchange}


@pytest.mark.parametrize("fault", sorted(TRAIN_FAULTS))
@pytest.mark.parametrize("name", TRAIN)
def test_train_fault_is_caught(name, fault, monkeypatch):
    TRAIN_FAULTS[fault](monkeypatch)
    res = run_tiny(name)
    assert not res["correct"], res["checks"]


def _with(cell, section, key, value):
    cfg = copy.deepcopy(cell.config)
    cfg[section][key] = value
    return dataclasses.replace(cell, config=cfg)


@pytest.mark.parametrize("section", ["data", "model", "topology", "inference"])
def test_unread_config_key_is_refused(section):
    """A key that no program path reads stops the run before it starts."""
    cell = _with(tiny.tiny_cell(TRAIN[0]), section, "unheard_of", 1)
    with pytest.raises((ValueError, TypeError)):
        run.run_cell(cell.name, SEED, 1.0, False, cell=cell,
                     devices=jax.devices(), say=lambda *_: None)


def test_unread_traffic_key_is_refused():
    cell = tiny.tiny_cell(TRAIN[0])
    cell = dataclasses.replace(cell, traffic={**cell.traffic,
                                              "rate_per_s": 1})
    with pytest.raises(ValueError):
        run.run_cell(cell.name, SEED, 1.0, False, cell=cell,
                     devices=jax.devices(), say=lambda *_: None)


@pytest.mark.parametrize("section,key,value", [
    ("data", "partition", "by_label"),
    ("data", "dataset", "fmnist_like"),
    ("inference", "wire_dtype", "bf16"),
])
def test_reference_refuses_what_it_does_not_implement(section, key, value):
    """The program runs these; the reference refuses them rather than
    compare against another computation."""
    cell = _with(tiny.tiny_cell(TRAIN[0]), section, key, value)
    ref = cells.reference_module(cell.config)
    with pytest.raises(ValueError):
        ref.train_readings(cell.config, cell.traffic, SEED, 1)


def test_unknown_driver_does_not_resolve():
    with pytest.raises((ImportError, FileNotFoundError)):
        cells.driver_module("no_such_driver")

# The paper's primary contribution: the decentralized Bayesian learning rule
# (posteriors + log-pool consensus + graphs + Theorem-1 theory + the
# simulated multi-agent runtime).  Production distribution lives in launch/.
from repro.core.posterior import (
    FullCovGaussian,
    GaussianPosterior,
    consensus_all_agents,
    consensus_full_cov,
    consensus_mean_field,
    consensus_mean_only,
    init_posterior,
    kl_gaussian,
    linreg_bayes_update,
    softplus,
    softplus_inv,
)
from repro.core import discrete, graphs, theory
from repro.core.flat import (
    FlatLayout,
    FlatPosterior,
    consensus_flat,
    flat_posterior_from_pytree,
    init_flat_posterior,
    make_flat_nll,
)
from repro.core.simulated import (
    NetworkState,
    as_w_schedule,
    init_network,
    make_round_fn,
    run_rounds,
)

__all__ = [
    "FlatLayout",
    "FlatPosterior",
    "consensus_flat",
    "flat_posterior_from_pytree",
    "init_flat_posterior",
    "make_flat_nll",
    "FullCovGaussian",
    "GaussianPosterior",
    "consensus_all_agents",
    "consensus_full_cov",
    "consensus_mean_field",
    "consensus_mean_only",
    "init_posterior",
    "kl_gaussian",
    "linreg_bayes_update",
    "softplus",
    "softplus_inv",
    "discrete",
    "graphs",
    "theory",
    "NetworkState",
    "as_w_schedule",
    "init_network",
    "make_round_fn",
    "run_rounds",
]

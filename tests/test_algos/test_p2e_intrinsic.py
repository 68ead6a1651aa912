"""Plan2Explore intrinsic-reward sanity: does ensemble disagreement actually
behave like an exploration signal? Two properties of the P2E-DV3 ensemble machinery:

1. training the ensemble on a fixed transition set DRIVES DISAGREEMENT DOWN
   on that set (seen data stops being interesting),
2. after training, disagreement is HIGHER on unseen inputs than on the
   training set (novelty ranks above familiarity).
"""

import jax
import jax.numpy as jnp
import numpy as np
import optax

from sheeprl_tpu.algos.p2e_dv3.agent import Ensemble, ensemble_apply, init_ensembles
from sheeprl_tpu.ops.distributions import MSEDistribution


def _disagreement(ens, params, x):
    preds = ensemble_apply(ens, params, x)  # [N, B, S]
    return float(preds.var(axis=0).mean())


def test_ensemble_disagreement_decreases_on_seen_data_and_ranks_novelty():
    key = jax.random.PRNGKey(0)
    in_dim, out_dim, n_members = 12, 6, 5
    ens = Ensemble(output_dim=out_dim, mlp_layers=2, dense_units=32)
    k_init, k_x, k_y, k_novel = jax.random.split(key, 4)
    params = init_ensembles(ens, n_members, k_init, jnp.zeros((1, in_dim)))

    # a fixed "seen" transition set with a deterministic target function
    x_seen = jax.random.normal(k_x, (64, in_dim))
    w = jax.random.normal(k_y, (in_dim, out_dim)) * 0.3
    y_seen = jnp.tanh(x_seen @ w)
    x_novel = 3.0 + 2.0 * jax.random.normal(k_novel, (64, in_dim))  # off-distribution

    tx = optax.adam(3e-3)
    opt = tx.init(params)

    # the exploration loss of p2e_dv3_exploration.py:237-243: sum over
    # members of the per-member mean MSE NLL against the shared target
    @jax.jit
    def step(params, opt):
        def loss_fn(p):
            outs = ensemble_apply(ens, p, x_seen)
            logp = MSEDistribution(outs, dims=1).log_prob(jnp.broadcast_to(y_seen[None], outs.shape))
            return -logp.mean(axis=1).sum()

        loss, grads = jax.value_and_grad(loss_fn)(params)
        updates, opt = tx.update(grads, opt, params)
        return optax.apply_updates(params, updates), opt, loss

    before = _disagreement(ens, params, x_seen)
    for _ in range(300):
        params, opt, _ = step(params, opt)
    after = _disagreement(ens, params, x_seen)

    assert after < before * 0.5, (
        f"disagreement on seen data should collapse with training: {before} -> {after}"
    )
    novel = _disagreement(ens, params, x_novel)
    assert novel > after * 2, (
        f"novel inputs should stay more 'interesting' than trained ones: seen={after}, novel={novel}"
    )

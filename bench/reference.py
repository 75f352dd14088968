"""Plain reference of the PFELS round, written from the algorithm alone.

It imports nothing of the program and takes nothing the program made: it
builds its own weights from the key the benchmark derives from ``--seed``,
draws every random quantity from the same documented key lanes, and runs
the round in straightforward ``jax.numpy``:

- the model: the configuration's own plain reference under
  ``models/`` (``init_params``, ``loss``), its weights made from the key;
- local training: tau steps of SGD with momentum on a batch of B samples
  drawn with replacement, each gradient clipped to global norm C1
  (Alg. 2 lines 5-11);
- rand_k support: the first k of a uniform permutation of [d] (Eq. 9);
- Theorem-5 beta: the smaller of the power cap
  min_i |h_i| sqrt(d P_i) / (C1 eta tau sqrt(k)) and the privacy cap
  eps / C2 (Eq. 21);
- AirComp: y = sum_i |h_i| (beta / |h_i|) A Delta_i + z, z ~ N(0, sigma0^2),
  Delta_hat = A^T y / (r beta), params += Delta_hat (Alg. 2 lines 12-16);
- the ledger: eps_t = min(C2 beta, eps), summed over rounds (Thm 3);
- the client bank: each sampled client's lane key fold_in(ks[5], id) and
  its participation count.

Key lanes (the round key is split in 7): 0 cohort, 1 local training,
2 gains, 3 support, 4 channel noise, 5 bank, 6 CSI. The initial key is
split once more: power limits from the init key itself, the round stream
from ``fold_in(init, 0x5047)``.

``dtype`` selects the precision: float32 under ``highest`` matmul
precision is the reference; bfloat16 throughout is the control, the
precision one step below what the configuration states.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax.flatten_util import ravel_pytree

RUN_STREAM_TAG = 0x5047
LANES = 7


# ------------------------------------------------------------------ round

def _local_train(params, x, y, key, *, net, model, fl, dtype):
    """tau clipped-SGD steps with momentum; returns (update, mean loss)."""
    lr, mom, clip = (jnp.asarray(fl[k], dtype)
                     for k in ("local_lr", "momentum", "clip"))

    def step(carry, k):
        p, v = carry
        idx = jax.random.randint(k, (fl["batch_size"],), 0, x.shape[0])
        val, g = jax.value_and_grad(net.loss)(p, model, x[idx], y[idx])
        norm = jnp.sqrt(sum(jnp.sum(jnp.square(a))
                            for a in jax.tree.leaves(g)))
        scale = jnp.minimum(jnp.asarray(1, dtype), clip / jnp.maximum(
            norm, jnp.asarray(1e-12, dtype)))
        v = jax.tree.map(lambda v_, g_: mom * v_ + g_ * scale, v, g)
        p = jax.tree.map(lambda p_, v_: p_ - lr * v_, p, v)
        return (p, v), val

    v0 = jax.tree.map(jnp.zeros_like, params)
    (p, _), losses = jax.lax.scan(step, (params, v0),
                                  jax.random.split(key, fl["local_steps"]))
    update = jax.tree.map(lambda a, b: a - b, p, params)
    return ravel_pytree(update)[0], jnp.mean(losses)


def c2_coefficient(fl):
    """C2 of Eq. (21), with delta = 1/N and sigma0 the receiver noise."""
    n, r = fl["num_clients"], fl["clients_per_round"]
    delta = 1.0 / n
    return (2.0 * math.sqrt(2.0) * fl["local_lr"] * fl["local_steps"]
            * fl["clip"] * r * math.sqrt(math.log(1.25 * r / (n * delta)))
            / (n * fl["noise_std"]))


def power_limits(init_key, fl, d):
    """P_i = SNR_i d sigma0^2, SNR_i uniform in the configured dB range."""
    lo, hi = fl["snr_db_range"]
    snr_db = jax.random.uniform(init_key, (fl["num_clients"],),
                                minval=lo, maxval=hi)
    return 10.0 ** (snr_db / 10.0) * float(d) * fl["noise_std"] ** 2


def support_size(fl, d):
    return max(int(round(fl["compression_ratio"] * d)), 1)


def _round(params, p_lim, round_key, data, *, cohort, net, model, fl,
           dtype, keep):
    """One round. ``cohort(data, sel)`` gives the sampled clients' samples;
    ``keep`` clients (all by default) reach the receiver."""
    r, n = fl["clients_per_round"], fl["num_clients"]
    flat, unravel = ravel_pytree(params)
    d = flat.shape[0]
    k = support_size(fl, d)
    ks = jax.random.split(round_key, LANES)
    sel = jax.random.choice(ks[0], n, (r,), replace=False)
    cx, cy = cohort(data, sel)
    train = functools.partial(_local_train, net=net, model=model, fl=fl,
                              dtype=dtype)
    updates, losses = jax.lax.map(
        lambda a: train(params, a[0].astype(dtype), a[1], a[2]),
        (cx, cy, jax.random.split(ks[1], r)))

    gains = jnp.clip(jax.random.exponential(ks[2], (r,)) * fl["gain_mean"],
                     *fl["gain_clip"]).astype(dtype)
    idx = jax.random.permutation(ks[3], d)[:k]
    cap_power = jnp.min(gains * jnp.sqrt(float(d) * p_lim[sel].astype(dtype))
                        / (fl["clip"] * fl["local_lr"] * fl["local_steps"]
                           * math.sqrt(k)))
    c2 = c2_coefficient(fl)
    beta = jnp.minimum(cap_power, jnp.asarray(fl["epsilon"] / c2, dtype))
    noise = (fl["noise_std"] * jax.random.normal(ks[4], (k,))).astype(dtype)

    heard = (jnp.arange(r) < keep).astype(dtype)
    signals = (heard * beta / gains)[:, None] * updates[:, idx]
    y = gains @ signals + noise
    delta_hat = jnp.zeros((d,), dtype).at[idx].set(y) / (keep * beta)
    metrics = {
        "train_loss": jnp.mean(losses),
        "update_norm": jnp.mean(jnp.sqrt(jnp.sum(jnp.square(updates), 1))),
        "beta": beta,
        "energy": jnp.sum(jnp.square(signals)),
        "eps_round": jnp.minimum(jnp.asarray(c2, dtype) * beta,
                                 jnp.asarray(fl["epsilon"], dtype)),
    }
    lanes = jax.vmap(lambda i: jax.random.fold_in(ks[5], i))(sel)
    return unravel(flat + delta_hat), delta_hat, metrics, sel, lanes


def run(seed_keys, net, model, fl, cohort, data, rounds, *,
        dtype=jnp.float32, keep=None):
    """``rounds`` rounds from the benchmark's keys, with the model ``net``
    (a module of ``models/``) of configuration ``model``. Returns a dict
    of host arrays: the initial and final flat parameters, the last
    round's Delta_hat, per-round metrics, the ledger's eps sum, and the
    bank's lanes and counts over all N clients."""
    keep = fl["clients_per_round"] if keep is None else keep
    prec = "highest" if dtype == jnp.float32 else "default"
    with jax.default_matmul_precision(prec):
        params = jax.jit(functools.partial(net.init_params, model=model))(
            seed_keys["weights"])
        params = jax.tree.map(lambda a: a.astype(dtype), params)
        flat0 = np.asarray(ravel_pytree(params)[0], np.float32)
        p_lim = power_limits(seed_keys["init"], fl, flat0.size)
        round_keys = jax.random.split(
            jax.random.fold_in(seed_keys["init"], RUN_STREAM_TAG), rounds)
        step = jax.jit(functools.partial(_round, cohort=cohort, net=net,
                                         model=model, fl=fl, dtype=dtype,
                                         keep=keep))
        n = fl["num_clients"]
        lanes = np.zeros((n,) + tuple(round_keys.shape[1:]), np.uint32)
        counts = np.zeros((n,), np.int32)
        per_round = []
        for t in range(rounds):
            params, delta_hat, m, sel, lane = step(params, p_lim,
                                                  round_keys[t], data)
            sel = np.asarray(sel)
            lanes[sel] = np.asarray(lane)
            counts[sel] += 1
            per_round.append({k: float(v) for k, v in m.items()})
    return {
        "flat0": flat0,
        "flat": np.asarray(ravel_pytree(params)[0], np.float32),
        "delta_hat": np.asarray(delta_hat, np.float32),
        "metrics": {k: np.array([m[k] for m in per_round])
                    for k in per_round[0]},
        "eps_sum": float(sum(m["eps_round"] for m in per_round)),
        "lanes": lanes, "counts": counts,
    }

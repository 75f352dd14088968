"""The full PFELS round (Alg. 2) and baselines, simulation mode.

``_build_cohort_core`` builds the one round body that ``repro.fl.Trainer``
runs, as its resident ``lax.scan`` or its streamed host loop (DESIGN.md
§8, §10): vmapped local training of the r sampled clients -> rand_k
projection -> Theorem-5 power control -> AirComp over the simulated MAC
-> server update. Baselines (WFL-P Eq. 36, WFL-PDP Eq. 37, DP-FedAvg
Alg. 1, FedAvg) share the same structure with their own aggregation.

Sharded cohort execution (``cfg.client_sharding="cohort"``, DESIGN.md §7):
the per-client pipeline (local training -> error-feedback add -> clip ->
rand-k -> power scaling) runs under ``shard_map`` with the r selected
clients partitioned over the ("pod", "data") mesh axes, and the AirComp
sum becomes a physical cross-device ``psum`` — the over-the-air
superposition as a distributed reduction. Three invariants keep it
numerically aligned with the vmapped single-device path:

  1. every PRNG draw (client sampling, per-client train keys, gains, rand-k
     support, channel noise) happens from the SAME keys as the vmapped
     path, outside the manual region or replicated inside it;
  2. the per-client flat updates come back sharded over the cohort axis, so
     the bank's error-feedback scatter-back and all metrics reuse the
     single-device code unchanged;
  3. the Theorem-5 ``beta`` is computed from the globally sampled gains
     before entering the manual region (it is a min over all r clients).
"""
from __future__ import annotations

import functools
from typing import Callable, Dict, Optional

import jax
import jax.numpy as jnp
from jax.flatten_util import ravel_pytree
from jax.sharding import Mesh, PartitionSpec as P

from repro.configs.base import PFELSConfig
from repro.core import aggregation, channel, channels, compressors, privacy
from repro.fl import algorithms
from repro.fl.client import local_train, model_update
from repro.kernels.pfels_transmit import ref as transmit_ref
from repro.launch.mesh import make_cohort_mesh
from repro.sharding import rules

_COHORT_AXES = ("pod", "data")

# The fixed 7-lane split of each round key (DESIGN.md §5). Which lane
# feeds which draw is a compatibility contract — pinned by
# tests/test_bank.py::test_key_lane_contract — because silently shifting
# a lane re-randomizes every stream in the round.
ROUND_KEY_LANES = {
    "selection": 0,      # Alg. 2 line 2 client sampling
    "client_train": 1,   # per-client local-training keys
    "gains": 2,          # channel-model step: gains (+ fold_in-derived
                         # draws such as the dropout mask, DESIGN.md §11)
    "support": 3,        # rand-k support omega_t
    "channel_noise": 4,  # receiver noise (or digital-aggregation noise)
    "bank": 5,           # ClientBank per-client lanes (DESIGN.md §10)
    "csi": 6,            # CSI estimation error (beyond paper)
}


def split_round_key(key):
    """The per-round 7-subkey split (DESIGN.md §5) — both execution paths
    (the Trainer's resident scan and its streamed loop) consume lanes from
    this one split."""
    return jax.random.split(key, len(ROUND_KEY_LANES))


def sample_cohort(key, n: int, r: int):
    """Alg. 2 line 2: sample r of n clients without replacement. ``key``
    must be the round's ``selection`` lane."""
    return jax.random.choice(key, n, (r,), replace=False)


def init_power_limits(key, cfg: PFELSConfig, d: int) -> jnp.ndarray:
    """(N,) per-device power limits P_i, as ``Trainer.init`` draws them
    (same key => same limits)."""
    return channel.sample_power_limits(key, cfg.num_clients, d, cfg.channel)


def _resolve_cohort_mesh(cfg: PFELSConfig,
                         mesh: Optional[Mesh]) -> Optional[Mesh]:
    """The mesh the cohort will shard over, or None for the vmapped path.
    With ``client_sharding="cohort"`` and no explicit mesh, builds one over
    the visible devices sized to divide ``clients_per_round``."""
    if cfg.client_sharding == "none":
        return None
    if cfg.client_sharding != "cohort":
        raise ValueError(
            f"unknown client_sharding mode {cfg.client_sharding!r}")
    return mesh if mesh is not None else make_cohort_mesh(
        cfg.clients_per_round)


def _cohort_shards(cfg: PFELSConfig, mesh: Optional[Mesh]) -> int:
    """Static shard count the round will actually use: the ('pod','data')
    extent of `mesh` when it divides r, else 1 — the drop-to-replicated
    convention of ``sharding.rules.resolve_spec`` applied to the client
    dim."""
    if mesh is None or cfg.client_sharding == "none":
        return 1
    n = rules.cohort_axis_size(mesh)
    if n <= 1 or cfg.clients_per_round % n != 0:
        return 1
    return n


def local_train_slice(cfg: PFELSConfig, loss_fn: Callable) -> Callable:
    """Local training (Alg. 2 lines 5-11) vmapped over a slice of clients:
    ``(params, cx, cy, ck) -> ((n, d) flat updates, (n,) losses)``."""
    train = functools.partial(
        local_train, loss_fn=loss_fn, steps=cfg.local_steps,
        lr=cfg.local_lr, clip=cfg.clip, momentum=cfg.momentum)

    def train_slice(params, cx, cy, ck):
        new_params, losses = jax.vmap(
            lambda x, y, k: train(params, x, y, k))(cx, cy, ck)
        updates = jax.vmap(lambda np_: model_update(params, np_))(new_params)
        flat = jax.vmap(lambda u: ravel_pytree(u)[0])(updates)
        return flat, losses
    return train_slice


# Room kept beside local training's own peak, in (n, d) float32 arrays of
# updates: compiled for a v5e at r = 32, the whole round's peak exceeds
# local training's by 0.6 of one for ViT-S/16 and 0.3 for ResNet-18, and
# the aggregation pads the updates into a second one. Two leave ViT-S/16
# at 224 px chunks of 2 clients (a compiled round peak of 9.6 GiB; 14.4
# GiB at 4) and the CNNs their whole cohort.
ROUND_UPDATE_COPIES = 2


def local_train_footprint(train_slice: Callable, params, cx, cy, ck,
                          chunk: int) -> Optional[int]:
    """Device bytes that training the n clients of ``cx`` ``chunk`` at a
    time asks of the round: the compiled peak of ``train_slice`` on one
    chunk, the other chunks' flat updates, and ``ROUND_UPDATE_COPIES``
    more copies of all n updates. None where the compiler finds that one
    chunk does not fit the device. The arguments are
    ``jax.ShapeDtypeStruct``s of the whole slice; a ``sharding`` on them
    selects the device compiled for."""
    def part(s):
        return jax.ShapeDtypeStruct((chunk,) + s.shape[1:], s.dtype,
                                    sharding=s.sharding)
    try:
        compiled = jax.jit(train_slice).lower(
            params, part(cx), part(cy), part(ck)).compile()
    except jax.errors.JaxRuntimeError as e:
        if "RESOURCE_EXHAUSTED" in str(e):
            return None
        raise
    mem = compiled.memory_analysis()
    n, per_client = cx.shape[0], mem.output_size_in_bytes // chunk
    return (mem.peak_memory_in_bytes
            + (n - chunk + ROUND_UPDATE_COPIES * n) * per_client)


def client_chunk(train_slice: Callable, params, cx, cy, ck,
                 memory_stats) -> int:
    """How many of the n clients of ``cx`` train at once: all n where
    their :func:`local_train_footprint` fits the device memory not in use,
    ``bytes_limit - bytes_in_use`` of the allocator's ``memory_stats``, or
    where the backend keeps no statistics; otherwise n's smallest divisor
    above 1 where that fits, else 1.

    Once the cohort does not fit whole, a larger chunk buys memory, not
    throughput: for ViT-S/16 at B = 50 a chunk of 2 already gives each
    vmapped op 100 x 197 tokens, while every client more in a chunk
    keeps its activations alive through the backward pass. The footprint
    grows with the chunk, so where the smallest divisor does not fit, no
    larger one does: a cohort that fits costs one compile, and one that
    does not costs two."""
    n = cx.shape[0]
    if not memory_stats or "bytes_limit" not in memory_stats:
        return n
    free = memory_stats["bytes_limit"] - memory_stats.get("bytes_in_use", 0)

    def fits(chunk):
        need = local_train_footprint(train_slice, params, cx, cy, ck, chunk)
        return need is not None and need <= free
    if fits(n):
        return n
    chunk = next((c for c in range(2, n) if n % c == 0), 1)
    return chunk if chunk > 1 and fits(chunk) else 1


def train_in_chunks(train_slice: Callable, params, cx, cy, ck,
                    chunk: int):
    """``train_slice`` over the n clients ``chunk`` at a time: vmapped
    within a chunk, ``lax.map`` over the chunks; ``chunk == n`` is
    ``train_slice`` itself. Each client keeps its own key, so every chunk
    size gives the same updates, to float rounding."""
    n = cx.shape[0]
    if chunk == n:
        return train_slice(params, cx, cy, ck)
    flat, losses = jax.lax.map(
        lambda a: train_slice(params, *a),
        jax.tree.map(lambda a: a.reshape((n // chunk, chunk) + a.shape[1:]),
                     (cx, cy, ck)))
    return flat.reshape(n, -1), losses.reshape(n)


def device_memory():
    """The first local device's allocator statistics, or None where the
    backend keeps none (the CPU)."""
    return jax.local_devices()[0].memory_stats()


def _build_cohort_core(cfg: PFELSConfig, loss_fn: Callable, d: int,
                       unravel: Callable, mesh: Optional[Mesh] = None):
    """The raw (un-jitted) round body on COHORT slices, uniform across
    algorithms AND channel models: ``cohort_core(params, p_sel, cx, cy,
    ks, res_sel, prev_delta, chan_carry, sel, t, eps_spent) ->
    (new_params, metrics, new_res_sel, delta_hat, new_chan_carry)`` where
    every client-indexed input/output is the sampled r-client slice —
    ``p_sel`` (r,), ``cx``/``cy`` (r, samples, ...), ``res_sel`` (r, d)
    or None — and ``ks`` is the ``split_round_key`` output (lanes 1-6
    consumed here; selection/bank lanes 0 and 5 belong to the caller).
    ``t`` (the absolute round counter) and ``eps_spent`` (the ledger's
    running sum) feed the CompressionSchedule (DESIGN.md §13).

    The wireless scenario resolves through the ``repro.core.channels``
    registry (DESIGN.md §11): ``chan_carry`` is the model's cross-round
    state pytree (None for stateless models) and ``sel`` the sampled
    client ids (stateful models index their per-client state by id).
    The model's ``step`` consumes the gains/csi lanes, its post-combining
    ``noise_std`` replaces the raw sigma_0 everywhere (receiver draw, β
    privacy cap via the registry hooks, ledger spend), and its optional
    transmit mask routes the realized-r aggregation paths.

    Population tensors never enter: this is what lets the streamed
    ClientBank (DESIGN.md §10) run the identical compiled body on
    host-gathered cohorts with device memory independent of n. With
    ``cfg.client_sharding="cohort"`` and a multi-device `mesh`, the
    per-client pipeline is shard_mapped over the cohort axis (module
    docstring)."""
    k_coords = max(int(round(cfg.compression_ratio * d)), 1)
    alg = algorithms.get_algorithm(cfg.algorithm)
    chan_model = channels.get_channel_model(cfg.channel.model)
    sigma0 = chan_model.noise_std(cfg.channel)
    has_mask = chan_model.may_mask(cfg.channel)
    r = cfg.clients_per_round
    aircomp = alg.aircomp
    n_shards = _cohort_shards(cfg, mesh)

    # ---- compressor statics (DESIGN.md §13): the registry entry only
    # applies to sparsifying AirComp schemes (pfels); wfl_* transmit dense
    # and dp_fedavg/fedavg are digital. Everything here is config-static,
    # so the rand_k default traces the exact pre-registry code paths.
    comp = (compressors.get_compressor(cfg.compressor)
            if aircomp and alg.sparsifies_transmit else None)
    sched = cfg.schedule
    sched_on = comp is not None and compressors.schedules.is_active(sched)
    has_encode = comp is not None and comp.encode is not None
    # carry-compressors (top_k_ef) force error feedback on: without the
    # residual memory, pure top-k starves never-transmitted coordinates
    ef_on = cfg.error_feedback or (comp is not None and comp.carry(cfg))
    c1_scale = comp.sensitivity(cfg, d) if comp is not None else 1.0
    # whether Support.active can be non-None this config (static, so the
    # sharded body's fixed signature knows to consume its ``act`` slot)
    dyn_active = comp is not None and (
        comp.dynamic_support(cfg)
        or (sched_on and sched.k_end_ratio < 1.0))
    # encode must see the CLIPPED update (clip -> quantize -> transmit is
    # the Lemma-2 premise the sensitivity factor is derived under), and
    # error feedback needs the clip scales for the residual — both cases
    # pre-apply the transmit clip and hand the aggregator clip=None
    pre_clip = cfg.transmit_clip is not None and (ef_on or has_encode)
    if comp is not None and comp.decode is not None and n_shards > 1:
        raise ValueError(
            f"compressor {comp.name!r} has a custom decode hook, which "
            f"the sharded-cohort path does not route yet; use "
            f"client_sharding='none' (DESIGN.md §13)")

    train_slice = local_train_slice(cfg, loss_fn)

    def client_updates(params, cx, cy, ck):
        """Local training (Alg. 2 lines 5-11) over any client slice ->
        ((r_slice, d) flat updates, (r_slice,) losses), in chunks of
        ``client_chunk`` clients: all of them, vmapped, where their
        compiled footprint fits the device's free memory or the backend
        keeps no statistics, else the smallest chunk that fits."""
        specs = jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype),
                             (params, cx, cy, ck))
        chunk = client_chunk(train_slice, *specs, device_memory())
        return train_in_chunks(train_slice, params, cx, cy, ck, chunk)

    def support_and_beta(gains_design, p_sel, prev_delta, idx_key, t,
                         eps_spent):
        """Registry hooks: support omega_t + β-design, from the GLOBAL (r,)
        gains — shared by both execution paths. ``gains_design`` must be
        ``channels.design_gains(cr)``: the gains the devices actually
        OBSERVE (``gains_obs`` under imperfect CSI — each device transmits
        ``x_i = (beta/h_i^est) A Delta_i``, so its energy is
        ``(beta/h_i^est)^2 ||A Delta_i||^2`` and the Eq. 34c power cap
        only bounds it by ``P_i`` when beta is designed from ``h^est``;
        designing from the true gains violated ``P_i`` whenever
        ``h_i < h_i^est``, regression-tested in
        tests/test_power_control.py), with dropped-out clients lifted so
        they never bind the min (they transmit nothing — the realized-r
        side of the DESIGN.md §11 mask contract).

        With an active :class:`CompressionSchedule` (DESIGN.md §13) the
        round counter ``t`` and the ledger's running spend anneal the
        live-slot column (ANDed into the support), the power limits, and
        the per-round ε ceiling — all traced, zero host round-trips."""
        with jax.named_scope("pfels.support"):
            sup = compressors.as_support(
                alg.select_support(cfg, d, k_coords, prev_delta, idx_key))
            if sched_on:
                ka = compressors.schedules.k_active(sched, cfg, k_coords, t)
                if ka is not None:
                    sup = compressors.and_active(sup, ka)
        with jax.named_scope("pfels.beta"):
            eps_t = None
            if sched_on:
                ps = compressors.schedules.power_scale(sched, cfg, t)
                if ps is not None:
                    p_sel = p_sel * ps
                eps_t = compressors.schedules.epsilon_round(sched, cfg, t,
                                                            eps_spent)
            k_used = compressors.support_size(sup)
            beta = alg.design_beta(cfg, gains_design, p_sel, d, k_used,
                                   epsilon=eps_t, c1_scale=c1_scale)
        return sup, beta, k_used

    def transmit_prelude(flat, qkeys):
        """The as-transmitted updates and the clip left to the aggregator,
        ``(agg_updates, agg_clip)``, shared by both execution paths. Error
        feedback needs the clip scales for the residual anyway (and encode
        must see the clipped update), so compute them ONCE here and hand
        the aggregator pre-clipped updates (clip=None) instead of paying a
        second full (r, d) norm sweep inside the fused kernel's
        client_sumsq pass."""
        agg_updates, agg_clip = flat, cfg.transmit_clip
        if pre_clip:
            agg_updates = flat * transmit_ref.clip_scales(
                flat, cfg.transmit_clip)[:, None]
            agg_clip = None
        if has_encode:
            agg_updates = comp.encode(cfg, agg_updates, qkeys)
        return agg_updates, agg_clip

    cohort_apply = None
    if n_shards > 1:
        spec_c = P(_COHORT_AXES)

        def cohort_body(params, cx_l, cy_l, ck_l, res_l, gains_l, gest_l,
                        mask_l, qk_l, idx, act, beta, noise_key):
            # gains_l is this shard's (r_local, M) per-antenna slice (M=1
            # for scalar channels — bit-exact identity, DESIGN.md §12)
            # inside the manual region: sharding constraints must not
            # re-reference the cohort axes
            with rules.exclude_axes(*_COHORT_AXES), \
                    jax.named_scope("pfels.local_train"):
                flat_l, losses_l = client_updates(params, cx_l, cy_l, ck_l)
                if ef_on:
                    flat_l = flat_l + res_l
            tx_l = flat_l
            if aircomp:
                with jax.named_scope("pfels.aircomp"):
                    # the as-transmitted updates go back sharded for the
                    # residual (compressors.sparsify of tx_l == what went
                    # on the air)
                    agg_updates, agg_clip = transmit_prelude(flat_l, qk_l)
                    tx_l = agg_updates
                    delta_hat, energy, _ = \
                        aggregation.aircomp_aggregate_sharded(
                            agg_updates, idx, gains_l, beta, noise_key,
                            d=d, sigma0=sigma0, r=r,
                            axis_name=_COHORT_AXES,
                            unbiased_rescale=cfg.unbiased_rescale,
                            gains_est_local=(
                                gest_l if cfg.channel.csi_error > 0
                                else None),
                            clip=agg_clip,
                            use_kernel=cfg.use_fused_kernel,
                            tx_mask_local=(mask_l if has_mask else None),
                            active=(act if dyn_active else None))
            else:
                # dp_fedavg / fedavg aggregate on the gathered updates
                # outside the manual region; only training is sharded
                delta_hat = jnp.zeros((d,), jnp.float32)
                energy = jnp.asarray(0.0, jnp.float32)
            return flat_l, losses_l, tx_l, delta_hat, energy

        # replication checking off: the cohort path communicates via
        # explicit psums
        cohort_apply = jax.shard_map(
            cohort_body, mesh=mesh,
            in_specs=(P(), spec_c, spec_c, spec_c, spec_c, spec_c, spec_c,
                      spec_c, spec_c, P(), P(), P(), P()),
            out_specs=(spec_c, spec_c, spec_c, P(), P()), check_vma=False)

    def cohort_core(params, p_sel, cx, cy, ks, res_sel, prev_delta,
                    chan_carry, sel, t, eps_spent):
        # each layer of the round runs under one flat ``pfels.*`` name
        # scope, which the compiled ops carry as op_name metadata into the
        # profiler's trace (PERF.md section 3 lists who reads each)
        with jax.named_scope("pfels.local_train"):
            ck = jax.random.split(ks[ROUND_KEY_LANES["client_train"]], r)
        with jax.named_scope("pfels.aircomp"):
            # stochastic-rounding keys: fold_in-derived from the support
            # lane (DESIGN.md §5 — the 7-lane round split stays pinned);
            # unused (DCE'd) unless the compressor encodes
            qk = jax.random.split(
                jax.random.fold_in(ks[ROUND_KEY_LANES["support"]],
                                   compressors.QUANT_STREAM_TAG), r)

        # ---- channel realization for this round (DESIGN.md §11): the
        # registered model consumes the gains/csi lanes and evolves its
        # cross-round carry; imperfect CSI (beyond paper): clients
        # precompensate with noisy gain estimates while the MAC applies
        # the true gains
        with jax.named_scope("pfels.channel"):
            new_chan_carry, cr = chan_model.step(
                chan_carry, cfg.channel, r, sel,
                ks[ROUND_KEY_LANES["gains"]], ks[ROUND_KEY_LANES["csi"]])
            if cr.tx_mask is not None and not has_mask:
                # a silent discard here would let beta design / r_realized
                # see the mask while aggregation ignores it — contradictory
                # numerics; fail at trace time instead
                raise ValueError(
                    f"channel model {chan_model.name!r} returned a tx_mask "
                    f"but its may_mask(cfg) hook says False — the mask "
                    f"plumbing is gated on may_mask (DESIGN.md §11)")
            gains = cr.gains
            gains_obs = channels.observed_gains(cr)
            gains_design = channels.design_gains(cr)
            tx_mask = cr.tx_mask

        sup = beta = None
        k_used = d
        if aircomp:
            # beta designed from what the devices observe (gains_obs ==
            # gains under perfect CSI) — the power cap must hold for the
            # precompensation the devices actually apply — with dropped
            # clients lifted out of the min (design_gains)
            sup, beta, k_used = support_and_beta(
                gains_design, p_sel, prev_delta,
                ks[ROUND_KEY_LANES["support"]],
                t, eps_spent)

        # ---- local training (lines 5-11) + error feedback [28-30]
        # (beyond-paper option, forced on by carry-compressors): add each
        # selected client's residual memory to its update before
        # sparsification; the untransmitted remainder is carried forward
        use_ef = ef_on and res_sel is not None
        agg_sharded = None
        tx_full = None    # the as-transmitted (clipped/encoded) updates
        if cohort_apply is not None:
            # the body names its own local_train and aircomp scopes: a
            # scope around the shard_map would nest them
            res_l = (res_sel if use_ef
                     else jnp.zeros((r, d), jnp.float32))
            gains_mat = (cr.gains_ant if cr.gains_ant is not None
                         else gains[:, None])
            flat_updates, losses, tx_sh, delta_sh, energy_sh = \
                cohort_apply(
                    params, cx, cy, ck, res_l, gains_mat, gains_obs,
                    (tx_mask if tx_mask is not None
                     else jnp.ones((r,), jnp.float32)),
                    qk,
                    sup.idx if sup is not None else jnp.arange(1),
                    (sup.active if sup is not None
                     and sup.active is not None
                     else jnp.ones((1,), jnp.float32)),
                    beta if beta is not None else jnp.asarray(1.0,
                                                              jnp.float32),
                    ks[ROUND_KEY_LANES["channel_noise"]])
            if aircomp:
                agg_sharded = (delta_sh, energy_sh)
                tx_full = tx_sh
        else:
            with jax.named_scope("pfels.local_train"):
                flat_updates, losses = client_updates(params, cx, cy, ck)
                if use_ef:
                    flat_updates = flat_updates + res_sel

        with jax.named_scope("pfels.local_train"):
            metrics: Dict[str, jnp.ndarray] = {
                "train_loss": jnp.mean(losses),
                "update_norm": jnp.mean(
                    jnp.linalg.norm(flat_updates, axis=1)),
                # == r unless the channel model masks transmissions
                # (dropout): the realized transmitter count of the
                # DESIGN.md §11 contract
                "r_realized": channels.realized_cohort_size(cr, r),
            }

        with jax.named_scope("pfels.aircomp"):
            if aircomp:
                if agg_sharded is not None:
                    delta_hat, energy = agg_sharded
                else:
                    agg_updates, agg_clip = transmit_prelude(flat_updates,
                                                             qk)
                    tx_full = agg_updates
                    agg_kw = dict(
                        d=d, sigma0=sigma0, r=r,
                        unbiased_rescale=cfg.unbiased_rescale,
                        gains_est=(cr.gains_obs if cfg.channel.csi_error > 0
                                   else None),
                        clip=agg_clip, tx_mask=tx_mask,
                        active=sup.active)
                    if cfg.use_fused_kernel:
                        # the whole scenario matrix rides the kernel
                        # in-tile: tx_mask as a coefficient column,
                        # per-antenna gains through the MRC combine
                        # (DESIGN.md §12)
                        delta_hat, energy, y_agg = \
                            aggregation.aircomp_aggregate_fused(
                                agg_updates, sup.idx, gains, beta,
                                ks[ROUND_KEY_LANES["channel_noise"]],
                                gains_ant=cr.gains_ant, **agg_kw)
                    else:
                        delta_hat, energy, y_agg = \
                            aggregation.aircomp_aggregate(
                                agg_updates, sup.idx, gains, beta,
                                ks[ROUND_KEY_LANES["channel_noise"]],
                                **agg_kw)
                    if comp is not None and comp.decode is not None:
                        # custom server-side reconstruction: the hook
                        # replaces the default A^T unprojection of the
                        # k-subcarrier payload; the 1/(r beta) unscale and
                        # the beyond-paper d/k unbiasing stay the round
                        # body's job
                        delta_hat = comp.decode(cfg, y_agg, sup, d) / (
                            aggregation.realized_r(tx_mask, r) * beta)
                        if cfg.unbiased_rescale:
                            delta_hat = delta_hat * (d / k_coords)
                metrics.update(beta=beta, energy=energy,
                               subcarriers=jnp.asarray(k_used))
            else:   # digital server-side aggregation (registry hook)
                # a dropped client uploads nothing in the digital
                # schemes too
                agg_in = (flat_updates * tx_mask[:, None]
                          if tx_mask is not None else flat_updates)
                delta_hat = alg.server_aggregate(
                    cfg, agg_in, ks[ROUND_KEY_LANES["channel_noise"]],
                    d=d, r=r)
                if tx_mask is not None:
                    # same realized-r contract as the AirComp paths: the
                    # hook averaged over the nominal r, so rescale to the
                    # mean of the updates actually RECEIVED (for dp_fedavg
                    # this also scales its noise by r/r_eff >= 1 —
                    # conservative). An all-dropped round received
                    # NOTHING: apply no update rather than an
                    # r-fold-amplified pure-noise step
                    delta_hat = jnp.where(
                        jnp.sum(tx_mask) > 0,
                        delta_hat * (r / aggregation.realized_r(tx_mask, r)),
                        jnp.zeros_like(delta_hat))
                metrics.update(beta=jnp.asarray(0.0), energy=jnp.asarray(0.0),
                               subcarriers=jnp.asarray(d))

        # ---- error-feedback memory update: e_i <- u_i - A^T A q(s_i u_i)
        # — the residual is the raw update minus what was ACTUALLY sent
        # (clipped, encoded, projected onto the live support), so the
        # clipped-away / quantization-lost fraction stays in the memory.
        # ``compressors.sparsify`` is THE projection definition every
        # aggregation path shares (ISSUE 7 satellite: this block no longer
        # re-implements it). ``tx_full`` is the as-transmitted (r, d)
        # batch from whichever path aggregated — for the plain rand_k +
        # no-clip config it IS flat_updates, tracing the seed-exact code.
        # Returned as the (r, d) cohort slice; the caller (ClientBank)
        # owns the scatter into the (n, d) bank.
        new_res_sel = res_sel
        if use_ef:
            with jax.named_scope("pfels.bank"):
                base = tx_full if (aircomp and tx_full is not None) \
                    else flat_updates
                if alg.sparsifies_transmit:
                    transmitted = jax.vmap(
                        lambda u: compressors.sparsify(u, sup, d))(base)
                else:
                    transmitted = base
                if tx_mask is not None:
                    # a dropped client transmitted NOTHING: its whole
                    # update stays in the residual memory for its next
                    # participation
                    transmitted = transmitted * tx_mask[:, None]
                new_res_sel = flat_updates - transmitted

        # ---- server update (line 16)
        with jax.named_scope("pfels.server"):
            flat_params, _ = ravel_pytree(params)
            new_flat = flat_params + delta_hat
            new_params = unravel(new_flat)
        return new_params, metrics, new_res_sel, delta_hat, new_chan_carry

    return cohort_core


def round_epsilon_spent(cfg: PFELSConfig, beta: float,
                        d: Optional[int] = None) -> float:
    """Per-round eps actually consumed (Thm 3 inverse), for the ledger.
    Uses the channel model's POST-COMBINING noise std (== the raw sigma_0
    for single-antenna models): the intrinsic noise that actually
    perturbs the aggregate is what the DP guarantee rides on
    (DESIGN.md §11) — and, for sparsifying AirComp schemes, C1 scaled by
    the configured compressor's sensitivity factor (DESIGN.md §13), so
    host recomputations (``PrivacyLedger``) reproduce the in-graph spend
    exactly; ``d`` feeds dimension-dependent factors (stoch_quant)."""
    alg = algorithms.get_algorithm(cfg.algorithm)
    s = (compressors.sensitivity_factor(cfg, d)
         if alg.aircomp and alg.sparsifies_transmit else 1.0)
    return privacy.round_epsilon(
        beta, cfg.local_lr, cfg.local_steps, cfg.clip * s,
        cfg.clients_per_round, cfg.num_clients, cfg.resolved_delta(),
        channels.effective_noise_std(cfg.channel))


def evaluate(params, loss_fn, xt, yt, batch: int = 256):
    """Test accuracy over the held-out set."""
    n = xt.shape[0]
    accs, losses = [], []
    for i in range(0, n, batch):
        loss, aux = loss_fn(params, {"x": xt[i:i + batch],
                                     "y": yt[i:i + batch]})
        accs.append(aux["accuracy"] * min(batch, n - i))
        losses.append(loss * min(batch, n - i))
    return (float(sum(losses)) / n, float(sum(accs)) / n)

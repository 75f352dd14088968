"""End-to-end training drivers.

Two modes:
  - ``simulation`` (default): the faithful paper set-up — N simulated edge
    devices, r sampled per round, exact rand_k + AirComp channel, driven
    through the unified ``repro.fl.Trainer`` API (each evaluation chunk is
    one compiled ``lax.scan`` program; the (ε, δ) ledger lives inside the
    compiled ``TrainState``).
  - ``production``: PFELS-as-distributed-optimizer over the mesh (pods =
    clients; DESIGN.md §3), for LLM-scale training on real hardware.

Usage (CPU-scale example):
  PYTHONPATH=src python -m repro.launch.train --mode simulation \
      --algorithm pfels --rounds 100 --epsilon 1.5 --p 0.3
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import time
from typing import Any

import jax

from repro.configs.base import CompressionSchedule, PFELSConfig
from repro.configs.paper_models import (BENCH_CNN_CIFAR, BENCH_MLP,
                                        PAPER_RESNET18_FEMNIST,
                                        PAPER_VGG11_CIFAR10)
from repro.core.channel import scaled_channel
from repro.core.channels import list_channel_models
from repro.core.compressors import list_compressors
from repro.data import make_federated_classification, make_population_source
from repro.fl import Trainer, TrainState, list_algorithms
from repro.launch.compile_cache import enable_compile_cache
from repro.models import cnn

# --model choices: the CPU-scale stand-ins and the paper's full-width
# models (configs/paper_models.py)
MODELS = {"mlp": BENCH_MLP, "cnn": BENCH_CNN_CIFAR,
          PAPER_VGG11_CIFAR10.name: PAPER_VGG11_CIFAR10,
          PAPER_RESNET18_FEMNIST.name: PAPER_RESNET18_FEMNIST}


@dataclasses.dataclass
class Simulation:
    """Everything :func:`run_simulation` drives: the trainer, its initial
    state, and the federated train/test data."""
    cfg: PFELSConfig
    d: int
    trainer: Trainer
    state: TrainState
    x: Any
    y: Any
    xt: Any
    yt: Any


def build_simulation(args) -> Simulation:
    model_cfg = MODELS[args.model]
    key = jax.random.PRNGKey(args.seed)
    params = cnn.init_cnn(key, model_cfg)
    d = sum(p.size for p in jax.tree.leaves(params))
    # channel scenario (DESIGN.md §11): the regime-scaled fading floor,
    # specialized to the selected registry model
    chan = dataclasses.replace(
        scaled_channel(d), model=args.channel,
        num_antennas=args.antennas, markov_rho=args.markov_rho,
        dropout_prob=args.dropout_prob)
    cfg = PFELSConfig(
        num_clients=args.clients, clients_per_round=args.sampled,
        local_steps=args.tau, local_lr=args.lr, clip=args.clip,
        compression_ratio=args.p, epsilon=args.epsilon,
        rounds=args.rounds, momentum=args.momentum,
        algorithm=args.algorithm,
        dp_fedavg_sigma=args.dp_sigma,
        bank_backend=args.bank,
        compressor=args.compressor,
        quant_bits=args.quant_bits,
        threshold_frac=args.threshold_frac,
        error_feedback=args.error_feedback,
        transmit_clip=args.transmit_clip,
        schedule=CompressionSchedule(
            mode=args.schedule, k_end_ratio=args.k_end_ratio,
            power_end=args.power_end, eps_floor=args.eps_floor),
        channel=chan)
    image_shape = (model_cfg.in_channels, model_cfg.image_size,
                   model_cfg.image_size)
    if args.bank == "streamed" and args.dirichlet_alpha is None:
        # population-scale path (DESIGN.md §10): on-demand per-client
        # generation + host-side bank; no (n, samples, ...) tensor exists
        x, xt, yt = make_population_source(
            key, n_clients=cfg.num_clients, per_client=args.per_client,
            num_classes=model_cfg.num_classes, image_shape=image_shape)
        y = None
    else:
        x, y, xt, yt = make_federated_classification(
            key, n_clients=cfg.num_clients, per_client=args.per_client,
            num_classes=model_cfg.num_classes, image_shape=image_shape,
            alpha=args.dirichlet_alpha)
    loss_fn = lambda p, b: cnn.cnn_loss(p, model_cfg, b)
    trainer = Trainer(cfg, loss_fn, params)
    return Simulation(cfg=cfg, d=d, trainer=trainer,
                      state=trainer.init(key), x=x, y=y, xt=xt, yt=yt)


def run_simulation(args):
    """Train ``args.rounds`` rounds in chunks of ``args.eval_every``
    (one ``Trainer.run`` call each), evaluating after every chunk.
    Returns ``(out, sim, state)``: the JSON report (also written to
    ``args.out``), the :class:`Simulation` it built, and the final
    ``TrainState``."""
    sim = build_simulation(args)
    cfg, d, trainer, state = sim.cfg, sim.d, sim.trainer, sim.state
    history = []
    energy_total = 0.0
    t0 = time.time()
    while int(state.round) < cfg.rounds:
        chunk = min(args.eval_every, cfg.rounds - int(state.round))
        t_run = time.perf_counter()
        state, m = jax.block_until_ready(
            trainer.run(state, sim.x, sim.y, rounds=chunk))
        run_s = time.perf_counter() - t_run
        energy_total += float(m["energy"].sum())
        tl, acc = trainer.evaluate(state, sim.xt, sim.yt)
        history.append({"round": int(state.round) - 1,
                        "train_loss": float(m["train_loss"][-1]),
                        "test_acc": acc, "energy_cum": energy_total,
                        "subcarriers": int(m["subcarriers"][-1]),
                        "run_s": run_s,
                        "per_round": {k: m[k].tolist() for k in
                                      ("train_loss", "beta", "energy")}})
        print(f"[{cfg.algorithm}] round {int(state.round) - 1:4d} loss="
              f"{float(m['train_loss'][-1]):.3f} acc={acc:.3f} "
              f"energy={energy_total:.3e}", flush=True)
    totals = trainer.ledger_totals(state)
    out = {"config": {"algorithm": cfg.algorithm, "epsilon": cfg.epsilon,
                      "p": cfg.compression_ratio, "rounds": cfg.rounds,
                      "clients": cfg.num_clients, "d": d,
                      "channel": cfg.channel.model,
                      "compressor": cfg.compressor,
                      "schedule": cfg.schedule.mode},
           "history": history,
           "energy_total": energy_total,
           "privacy": {"per_round_eps_max": totals["eps_max_round"],
                       "basic_composition": totals["basic"],
                       "advanced_composition": totals["advanced"]},
           "wall_s": time.time() - t0}
    if args.out:
        with open(args.out, "w") as f:
            json.dump(out, f, indent=2)
    return out, sim, state


def build_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser()
    ap.add_argument("--mode", default="simulation",
                    choices=["simulation"])
    ap.add_argument("--algorithm", default="pfels",
                    choices=list_algorithms())
    ap.add_argument("--model", default="mlp", choices=sorted(MODELS),
                    help="model config: the CPU-scale 'mlp'/'cnn' "
                         "stand-ins or a full-width paper model")
    ap.add_argument("--clients", type=int, default=100)
    ap.add_argument("--sampled", type=int, default=16)
    ap.add_argument("--per-client", type=int, default=40)
    ap.add_argument("--tau", type=int, default=5)
    ap.add_argument("--lr", type=float, default=0.05)
    ap.add_argument("--clip", type=float, default=1.0)
    ap.add_argument("--p", type=float, default=0.3)
    ap.add_argument("--epsilon", type=float, default=1.5)
    ap.add_argument("--rounds", type=int, default=100)
    ap.add_argument("--momentum", type=float, default=0.9)
    ap.add_argument("--dp-sigma", type=float, default=1.0)
    ap.add_argument("--dirichlet-alpha", type=float, default=None)
    ap.add_argument("--channel", default="block_fading",
                    choices=list_channel_models(),
                    help="wireless scenario from the repro.core.channels "
                         "registry (DESIGN.md §11): block_fading is the "
                         "paper's i.i.d. flat fading; markov_fading "
                         "correlates gains across rounds; mimo_mrc gives "
                         "the base station --antennas receive antennas; "
                         "dropout drops each transmission w.p. "
                         "--dropout-prob")
    ap.add_argument("--antennas", type=int, default=4,
                    help="M receive antennas (mimo_mrc)")
    ap.add_argument("--markov-rho", type=float, default=0.9,
                    help="round-to-round gain correlation (markov_fading)")
    ap.add_argument("--dropout-prob", type=float, default=0.1,
                    help="per-round transmission dropout probability")
    ap.add_argument("--compressor", default="rand_k",
                    choices=list_compressors(),
                    help="update compressor from the "
                         "repro.core.compressors registry (DESIGN.md "
                         "§13): rand_k is the paper's sparsifier; "
                         "top_k_ef does magnitude top-k of the released "
                         "aggregate with mandatory error feedback; "
                         "threshold keeps coords above --threshold-frac "
                         "of the max; stoch_quant adds --quant-bits "
                         "unbiased stochastic quantization (its norm "
                         "inflation is charged to the privacy ledger)")
    ap.add_argument("--quant-bits", type=int, default=8,
                    help="signed quantization bits (stoch_quant)")
    ap.add_argument("--threshold-frac", type=float, default=0.1,
                    help="live-coordinate threshold as a fraction of "
                         "max|delta_hat| (threshold)")
    ap.add_argument("--error-feedback", action="store_true",
                    help="per-client error-feedback residual memory "
                         "(forced on by carry compressors like top_k_ef)")
    ap.add_argument("--transmit-clip", type=float, default=None,
                    help="per-client l2 cap on the transmitted update")
    ap.add_argument("--schedule", default="none",
                    choices=["none", "linear", "budget"],
                    help="CompressionSchedule mode (DESIGN.md §13): "
                         "'linear' anneals the live-k fraction to "
                         "--k-end-ratio and power to --power-end over "
                         "the rounds; 'budget' additionally paces the "
                         "per-round epsilon ceiling against the "
                         "remaining eps_total = epsilon * rounds")
    ap.add_argument("--k-end-ratio", type=float, default=1.0,
                    help="final live fraction of the k budget (schedule)")
    ap.add_argument("--power-end", type=float, default=1.0,
                    help="final power-limit multiplier (schedule)")
    ap.add_argument("--eps-floor", type=float, default=0.0,
                    help="per-round epsilon floor (budget schedule)")
    ap.add_argument("--bank", default="resident",
                    choices=["resident", "streamed"],
                    help="ClientBank backend (DESIGN.md §10): 'streamed' "
                         "keeps per-client state host-side and generates "
                         "cohort data on demand — num_clients can be "
                         "100_000+ with device memory independent of n")
    ap.add_argument("--eval-every", type=int, default=10)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--out", default=None)
    return ap


def main():
    enable_compile_cache()
    run_simulation(build_parser().parse_args())


if __name__ == "__main__":
    main()

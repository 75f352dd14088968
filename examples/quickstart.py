"""Quickstart: PFELS end-to-end on a synthetic federated image task.

Runs a few hundred FL rounds of Algorithm 2 (simulated wireless channel,
Theorem-5 power control, client-level DP accounting) through the unified
``Trainer``/``TrainState`` API — each evaluation chunk is one compiled
``lax.scan`` program, and the privacy ledger lives inside the compiled
state — then prints the privacy/communication/energy report.

  PYTHONPATH=src python examples/quickstart.py [--rounds 200]
"""
import argparse

import jax

from repro.configs import PFELSConfig
from repro.configs.paper_models import BENCH_CNN_CIFAR
from repro.core.channel import scaled_channel
from repro.data import make_federated_classification
from repro.fl import Trainer
from repro.launch.compile_cache import enable_compile_cache
from repro.models import cnn


def main():
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--rounds", type=int, default=200)
    ap.add_argument("--epsilon", type=float, default=1.5)
    ap.add_argument("--p", type=float, default=0.3)
    ap.add_argument("--eval-every", type=int, default=25)
    args = ap.parse_args()

    key = jax.random.PRNGKey(0)
    params = cnn.init_cnn(key, BENCH_CNN_CIFAR)
    x, y, xt, yt = make_federated_classification(
        key, n_clients=100, per_client=40, num_classes=10,
        image_shape=(3, 16, 16))
    loss_fn = lambda p, b: cnn.cnn_loss(p, BENCH_CNN_CIFAR, b)

    d = sum(p.size for p in jax.tree.leaves(params))
    # fading floor scaled to the paper's operating regime at reduced d
    cfg = PFELSConfig(num_clients=100, clients_per_round=8, local_steps=5,
                      local_lr=0.05, clip=1.0, compression_ratio=args.p,
                      epsilon=args.epsilon, rounds=args.rounds,
                      momentum=0.9, channel=scaled_channel(d))
    trainer = Trainer(cfg, loss_fn, params)
    state = trainer.init(key)
    print(f"model: {BENCH_CNN_CIFAR.name}  d={d}  "
          f"subcarriers/round={int(args.p * d)}")

    energy = 0.0
    while int(state.round) < cfg.rounds:
        chunk = min(args.eval_every, cfg.rounds - int(state.round))
        state, m = trainer.run(state, x, y, rounds=chunk)
        energy += float(m["energy"].sum())
        tl, acc = trainer.evaluate(state, xt, yt)
        print(f"round {int(state.round):4d}  "
              f"loss={float(m['train_loss'][-1]):.3f}  "
              f"test_acc={acc:.3f}  beta={float(m['beta'][-1]):.2f}  "
              f"energy={energy:.3e}")

    totals = trainer.ledger_totals(state)   # exact, from the compiled state
    (e_basic, d_basic), (e_adv, d_adv) = totals["basic"], totals["advanced"]
    print("\n--- PFELS report ---")
    print(f"per-round DP:       ({cfg.epsilon}, {cfg.resolved_delta():.1e})")
    print(f"T-round basic:      ({e_basic:.1f}, {d_basic:.1e})")
    print(f"T-round advanced:   ({e_adv:.1f}, {d_adv:.1e})")
    print(f"transmit energy:    {energy:.3e}")
    print(f"subcarriers/round:  {int(args.p * d)} of {d}")


if __name__ == "__main__":
    main()

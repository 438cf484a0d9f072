"""The paper's own setting: PreActResNet18 (GroupNorm) complex model,
first-2-stages + mix-pool simple model, federated over heterogeneous
clients on CIFAR-shaped data (non-IID Dirichlet split).

This is the full 11.1M/0.7M model pair — a handful of rounds takes a few
minutes on CPU.  For the paper protocol (100 clients, 1000 rounds) run
``launch/train.py --model resnet`` on real hardware.

Run:  PYTHONPATH=src python examples/federated_cifar.py [rounds]
"""

import sys

from repro.launch.train import main

if __name__ == "__main__":
    rounds = sys.argv[1] if len(sys.argv) > 1 else "3"
    # current driver surface (see launch/train.py --help): the flat-buffer
    # fold streamed in chunks of 2 clients, the f32 (paper-accounting)
    # wire, synchronous rounds.  Swap "--comm-dtype" to int8 for the
    # quantized wire, or add "--async-lag 1" for bounded-lag async rounds.
    main(["--model", "resnet", "--algorithm", "fedhen",
          "--rounds", rounds, "--clients", "8", "--participation", "0.25",
          "--local-epochs", "1", "--batch-size", "32",
          "--data-points", "1024", "--non-iid", "--eval-every", "1",
          "--cohort-chunk", "2", "--comm-dtype", "float32",
          "--async-lag", "0"])

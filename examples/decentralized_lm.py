"""End-to-end driver: decentralized LM pretraining with a learned topology.

Runs D-SGD over a (data x model) mesh of the devices present on a reduced
transformer for a few hundred steps with domain-skewed synthetic data --
the systems-scale version of the paper's experiments. Each device is one
D-SGD node; ``--full`` runs the published config (on a TPU).

    PYTHONPATH=src python examples/decentralized_lm.py --steps 200

On a CPU, ``XLA_FLAGS=--xla_force_host_platform_device_count=8`` in the
environment gives eight host devices, hence eight nodes.
"""

import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

from repro.launch.train import main  # the launcher is the public driver

if __name__ == "__main__":
    # default arguments: qwen3-0.6b smoke config, STL-FW topology
    if len(sys.argv) == 1:
        sys.argv += ["--arch", "qwen3-0.6b", "--steps", "200",
                     "--topology", "stl-fw", "--budget", "2", "--lr", "5e-3"]
    main()

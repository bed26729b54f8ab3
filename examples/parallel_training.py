"""Data-parallel training over the device mesh (reference ParallelWrapper /
Spark parameter averaging). On CPU run with:
  XLA_FLAGS=--xla_force_host_platform_device_count=8 JAX_PLATFORMS=cpu \
  python examples/parallel_training.py
"""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax

from deeplearning4j_tpu.datasets.mnist import MnistDataSetIterator
from deeplearning4j_tpu.models.lenet import lenet_mnist
from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
from deeplearning4j_tpu.parallel.wrapper import ParallelWrapper


def main():
    n = min(len(jax.devices()), 8)
    net = MultiLayerNetwork(lenet_mnist()).init()
    wrapper = (ParallelWrapper.builder(net)
               .workers(n)
               .averaging_frequency(1)
               .shard_optimizer_state()   # ZeRO-1: moments live 1/n per chip
               .build())
    it = MnistDataSetIterator(batch=16 * n, num_examples=4096)
    wrapper.fit(it, epochs=1)
    print(f"{n}-way DP done; score {net.score_value:.4f}")
    # proof the optimizer state is sharded, not replicated: the largest
    # moment tensor holds 1/n of its bytes per device
    leaf = max(jax.tree_util.tree_leaves(net.updater_state),
               key=lambda a: a.nbytes)
    frac = leaf.addressable_shards[0].data.nbytes / leaf.nbytes
    print(f"ZeRO-1: largest updater moment holds {frac:.0%} per device")
    test = MnistDataSetIterator(batch=256, train=False, num_examples=1024)
    print("accuracy:", net.evaluate(test).accuracy())


if __name__ == "__main__":
    main()

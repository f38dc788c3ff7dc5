"""CPU tests of the benchmark: ``python -m pytest bench/tests`` from the
root of the checkout.  JAX is held to the CPU, the kernels run in
interpret mode, and the cells run at small sizes."""
import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

#: Configuration keys a CPU test run changes, by configuration: a few
#: clients with a few dozen examples each; every width the chip runs
#: that a CPU can hold stays.
SMALL = {
    "paper_mnist_mlp": {"n_clients": 10, "n_train": 400, "hidden": 16},
    "paper_cifar_cnn": {"n_clients": 8, "n_train": 400, "image_hw": 8},
}

#: Traffic keys a CPU test run changes, by traffic mix: a short
#: ``run_rounds`` call, so that a window of a second holds a few.
SMALL_TRAFFIC = {
    "sync_rounds": {"chunk_rounds": 4},
}

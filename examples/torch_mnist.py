"""MNIST with the torch binding — the reference's flagship example
(† ``examples/pytorch/pytorch_mnist.py``) ported API-for-API.

Run multi-process (one rank per process, the reference topology):

    python -m horovod_tpu.runner -np 2 -- python examples/torch_mnist.py
(add ``--platform cpu`` before ``--`` on a CPU dev rig)
"""

import os

os.environ["XLA_FLAGS"] = os.environ.get("XLA_FLAGS", "") + \
    " --xla_force_host_platform_device_count=1"

import numpy as np  # noqa: E402
import torch  # noqa: E402
import torch.nn as nn  # noqa: E402
import torch.nn.functional as F  # noqa: E402

import horovod_tpu.torch as hvd  # noqa: E402


class Net(nn.Module):
    """† the reference example's Net."""

    def __init__(self):
        super().__init__()
        self.conv1 = nn.Conv2d(1, 10, kernel_size=5)
        self.conv2 = nn.Conv2d(10, 20, kernel_size=5)
        self.fc1 = nn.Linear(320, 50)
        self.fc2 = nn.Linear(50, 10)

    def forward(self, x):
        x = F.relu(F.max_pool2d(self.conv1(x), 2))
        x = F.relu(F.max_pool2d(self.conv2(x), 2))
        x = x.flatten(1)
        x = F.relu(self.fc1(x))
        return F.log_softmax(self.fc2(x), dim=1)


def main():
    hvd.init()
    torch.manual_seed(42)
    model = Net()
    # Horovod idioms, verbatim:
    hvd.broadcast_parameters(model.state_dict(), root_rank=0)
    optimizer = hvd.DistributedOptimizer(
        torch.optim.SGD(model.parameters(), lr=0.01 * hvd.size()),
        named_parameters=model.named_parameters())

    rng = np.random.RandomState(hvd.cross_rank())   # per-rank data shard
    x = torch.from_numpy(rng.rand(32, 1, 28, 28).astype(np.float32))
    y = torch.from_numpy(rng.randint(0, 10, size=(32,)))

    for epoch in range(3):
        optimizer.zero_grad()
        loss = F.nll_loss(model(x), y)
        loss.backward()
        optimizer.step()
        avg = hvd.allreduce(loss.detach(), hvd.Average)
        if hvd.cross_rank() == 0:
            print(f"epoch {epoch}: avg loss {float(avg):.4f}")


if __name__ == "__main__":
    main()

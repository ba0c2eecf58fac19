"""ResNet-50 ImageNet training with the torch binding — the reference's
stock example († ``examples/pytorch/pytorch_imagenet_resnet50.py``)
workflow, API-for-API, on the TPU-native runtime: per-parameter gradient
hooks → async allreduce on the XLA data plane, LR scaled by world size
with warmup, metric averaging across ranks, rank-0-only checkpointing.

The image has no ImageNet (and no network), so data is synthetic and
shaped by flags; torch compute runs on CPU while the collectives ride the
TPU/XLA path.  Defaults are smoke-test sized — pass ``--image-size 224
--batch-size 32`` for the real geometry.

Run:  hvdrun -np 2 python examples/torch_imagenet_resnet50.py
(add ``--platform cpu`` to the hvdrun flags on a CPU dev rig)
"""

import argparse
import os

os.environ["XLA_FLAGS"] = os.environ.get("XLA_FLAGS", "") + \
    " --xla_force_host_platform_device_count=1"

import numpy as np  # noqa: E402
import torch  # noqa: E402
import torch.nn as nn  # noqa: E402
import torch.nn.functional as F  # noqa: E402

import horovod_tpu.torch as hvd  # noqa: E402


def build_resnet50(num_classes: int = 1000) -> nn.Module:
    """torchvision's resnet50 when available (the reference example uses
    ``models.resnet50()``), else an equivalent in-file Bottleneck stack."""
    try:
        from torchvision import models
        return models.resnet50(num_classes=num_classes)
    except ImportError:
        pass

    class Bottleneck(nn.Module):
        expansion = 4

        def __init__(self, cin, width, stride=1):
            super().__init__()
            cout = width * self.expansion
            self.conv1 = nn.Conv2d(cin, width, 1, bias=False)
            self.bn1 = nn.BatchNorm2d(width)
            self.conv2 = nn.Conv2d(width, width, 3, stride, 1, bias=False)
            self.bn2 = nn.BatchNorm2d(width)
            self.conv3 = nn.Conv2d(width, cout, 1, bias=False)
            self.bn3 = nn.BatchNorm2d(cout)
            self.down = None
            if stride != 1 or cin != cout:
                self.down = nn.Sequential(
                    nn.Conv2d(cin, cout, 1, stride, bias=False),
                    nn.BatchNorm2d(cout))

        def forward(self, x):
            r = x if self.down is None else self.down(x)
            x = F.relu(self.bn1(self.conv1(x)))
            x = F.relu(self.bn2(self.conv2(x)))
            return F.relu(self.bn3(self.conv3(x)) + r)

    class ResNet50(nn.Module):
        def __init__(self, num_classes):
            super().__init__()
            self.stem = nn.Sequential(
                nn.Conv2d(3, 64, 7, 2, 3, bias=False), nn.BatchNorm2d(64),
                nn.ReLU(), nn.MaxPool2d(3, 2, 1))
            stages, cin = [], 64
            for width, blocks, stride in [(64, 3, 1), (128, 4, 2),
                                          (256, 6, 2), (512, 3, 2)]:
                for b in range(blocks):
                    stages.append(Bottleneck(cin, width,
                                             stride if b == 0 else 1))
                    cin = width * Bottleneck.expansion
            self.stages = nn.Sequential(*stages)
            self.fc = nn.Linear(cin, num_classes)

        def forward(self, x):
            x = self.stages(self.stem(x))
            return self.fc(x.mean(dim=(2, 3)))

    return ResNet50(num_classes)


def metric_average(val: float, name: str) -> float:
    """† the reference example's cross-rank metric averaging."""
    return float(hvd.allreduce(torch.tensor(val), name=name))


def main() -> None:
    p = argparse.ArgumentParser()
    p.add_argument("--batch-size", type=int, default=4)
    p.add_argument("--image-size", type=int, default=64)
    p.add_argument("--num-classes", type=int, default=100)
    p.add_argument("--epochs", type=int, default=2)
    p.add_argument("--steps-per-epoch", type=int, default=2)
    p.add_argument("--base-lr", type=float, default=0.0125)
    p.add_argument("--warmup-epochs", type=float, default=1)
    p.add_argument("--momentum", type=float, default=0.9)
    p.add_argument("--wd", type=float, default=5e-5)
    p.add_argument("--batches-per-allreduce", type=int, default=1,
                   help="† local gradient aggregation "
                        "(backward_passes_per_step)")
    p.add_argument("--fp16-allreduce", action="store_true")
    p.add_argument("--use-adasum", action="store_true")
    p.add_argument("--checkpoint-dir", default="")
    args = p.parse_args()

    hvd.init()
    torch.manual_seed(42)
    torch.set_num_threads(max(1, (os.cpu_count() or 2) // hvd.local_size()))

    model = build_resnet50(args.num_classes)

    # † lr scaled by total batch parallelism (Goyal et al. linear scaling);
    # Adasum converges at the local batch scale, so skip the size factor.
    lr_scale = args.batches_per_allreduce * (1 if args.use_adasum
                                             else hvd.size())
    optimizer = torch.optim.SGD(model.parameters(),
                                lr=args.base_lr * lr_scale,
                                momentum=args.momentum,
                                weight_decay=args.wd)
    compression = (hvd.Compression.fp16 if args.fp16_allreduce
                   else hvd.Compression.none)
    optimizer = hvd.DistributedOptimizer(
        optimizer, named_parameters=model.named_parameters(),
        compression=compression,
        op=hvd.Adasum if args.use_adasum else hvd.Average,
        backward_passes_per_step=args.batches_per_allreduce)

    # † step-0 sync: parameters and optimizer state from rank 0.
    hvd.broadcast_parameters(model.state_dict(), root_rank=0)
    hvd.broadcast_optimizer_state(optimizer, root_rank=0)

    # Synthetic per-rank shard, ImageNet geometry scaled by flags.
    rng = np.random.RandomState(1234 + hvd.cross_rank())
    n = args.batch_size * args.batches_per_allreduce
    steps = args.steps_per_epoch

    def make_batch():
        x = rng.rand(n, 3, args.image_size, args.image_size)
        y = rng.randint(0, args.num_classes, size=(n,))
        return (torch.from_numpy(x.astype(np.float32)),
                torch.from_numpy(y))

    warmup_steps = args.warmup_epochs * steps
    step = 0
    for epoch in range(args.epochs):
        model.train()
        running_loss = running_acc = 0.0
        for _ in range(steps):
            # † gradual LR warmup from base_lr to base_lr * scale.
            if step < warmup_steps:
                frac = (step + 1) / max(1.0, warmup_steps)
                for g in optimizer.param_groups:
                    g["lr"] = args.base_lr * (1 + frac * (lr_scale - 1))
            x, y = make_batch()
            optimizer.zero_grad()
            # † split into micro-batches; one allreduce per
            # batches_per_allreduce backward passes.
            for i in range(0, n, args.batch_size):
                out = model(x[i:i + args.batch_size])
                loss = F.cross_entropy(out, y[i:i + args.batch_size])
                loss.backward()
                running_loss += float(loss.detach()) / args.batches_per_allreduce
                running_acc += float((out.argmax(1) ==
                                      y[i:i + args.batch_size]).float()
                                     .mean()) / args.batches_per_allreduce
            optimizer.step()
            step += 1
        train_loss = metric_average(running_loss / steps, "avg_loss")
        train_acc = metric_average(running_acc / steps, "avg_accuracy")
        if hvd.rank() == 0:
            print(f"epoch {epoch}: loss={train_loss:.4f} "
                  f"acc={train_acc:.4f} lr={optimizer.param_groups[0]['lr']:.4f}")
            if args.checkpoint_dir:
                torch.save({"model": model.state_dict(),
                            "epoch": epoch},
                           os.path.join(args.checkpoint_dir,
                                        f"checkpoint-{epoch}.pt"))
    if hvd.rank() == 0:
        print("DONE resnet50", flush=True)


if __name__ == "__main__":
    main()

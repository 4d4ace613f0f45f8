"""End-to-end driver: train an HGNN on synthetic ACM through the unified
`repro.api` surface — one `ExecutorSpec` picks the NA executor (the
banded path runs the Pallas NA kernels forward and their custom VJPs
backward over one cached packing); `Session.compile` binds model and
batches; `CompiledHGNN.fit` trains with no backend kwargs.

  PYTHONPATH=src python examples/hgnn_train_acm.py [--steps 100]
      [--model rgat] [--na-executor jnp|banded] [--scale 1.0]

Note: the banded executor uses interpret-mode kernels on CPU — keep
--scale <= 0.25 with it unless you enjoy watching jaxprs unroll.
"""
import argparse
import time

from repro.api import ExecutorSpec, Session, device_features
from repro.compile_cache import enable_compile_cache
from repro.core.hgnn import HGNNConfig
from repro.hetero import make_dataset
from repro.train import propagated_feature_labels, semi_supervised_masks

enable_compile_cache()

ap = argparse.ArgumentParser()
ap.add_argument("--steps", type=int, default=100)
ap.add_argument("--model", default="rgat", choices=["rgcn", "rgat", "shgn"])
ap.add_argument("--na-executor", "--na-backend", dest="na_executor",
                default="jnp", choices=["jnp", "banded"])
ap.add_argument("--scale", type=float, default=1.0)
args = ap.parse_args()

g = make_dataset("ACM", scale=args.scale)
targets = ["APA", "PAP", "PSP", "PTP"]
sess = Session(ExecutorSpec(na_executor=args.na_executor))
compiled = sess.compile(g, targets, HGNNConfig(
    model=args.model, hidden=64, num_layers=3, num_classes=3,
    target_type="P"))
feats = device_features(g)

n = compiled.num_target
labels = propagated_feature_labels(compiled.semantic, targets, g.features, n)
masks = semi_supervised_masks(n, seed=0)

t0 = time.time()


def progress(step, loss):
    if step % 25 == 0 or step == args.steps - 1:
        print(f"step {step:4d}  loss {loss:.4f}  "
              f"({(time.time() - t0) / (step + 1):.2f}s/step)")


out = compiled.fit(feats, labels, masks, epochs=args.steps,
                   epoch_callback=progress)
print(f"done [{args.na_executor}]: train_acc {out['train_acc']:.3f}  "
      f"val_acc {out['val_acc']:.3f}  test_acc {out['test_acc']:.3f}")

"""Jitted semi-supervised HGNN training on either NA executor.

The banded NA kernels are differentiable (custom VJPs over the cached
``PackedEdges``, kernels/seg_sum.py and kernels/ops.py), so the same
train step runs over edge-list batches (the segment-sum reference) or
banded batches (the Pallas NA kernels): the batches pick the NA step
(``HGNN.hidden_states``), and ``kernel_backend`` is the one kernel
choice.  Semantic-graph batches are
closed over by the step function — they are host-side packings, not
pytrees — and because every VJP closure is memoized on its packing, a
jitted step retraces nothing across steps: one ``BandedBatch`` list
serves the whole training run (grad-safe reuse).

The task is the standard semi-supervised node classification setup of
the HGNN literature: full-graph forward, cross-entropy on a masked
train split, accuracy reported on held-out splits.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.train.optim import (
    AdamWState,
    adamw_init,
    adamw_update,
    clip_by_global_norm,
    warmup_cosine,
)


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class HGNNTrainState:
    """Parameters + optimizer state, one pytree (jit-transparent)."""

    params: Any
    opt: AdamWState


def init_train_state(model, key: jax.Array) -> HGNNTrainState:
    params = model.init(key)
    return HGNNTrainState(params=params, opt=adamw_init(params))


def semi_supervised_masks(
    num_nodes: int,
    seed: int = 0,
    train_frac: float = 0.6,
    val_frac: float = 0.2,
) -> Dict[str, jax.Array]:
    """Random train/val/test split as float32 masks (the loss multiplies
    by the mask, so masks — not index lists — keep the step shape-static)."""
    rng = np.random.default_rng(seed)
    perm = rng.permutation(num_nodes)
    n_train = int(round(num_nodes * train_frac))
    n_held = n_train + int(round(num_nodes * val_frac))
    splits = (
        ("train", perm[:n_train]),
        ("val", perm[n_train:n_held]),
        ("test", perm[n_held:]),
    )
    masks = {}
    for name, ids in splits:
        m = np.zeros(num_nodes, np.float32)
        m[ids] = 1.0
        masks[name] = jnp.asarray(m)
    return masks


def degree_bucket_labels(
    semantic: Dict[str, Any],
    targets: List[str],
    num_dst: int,
    num_classes: int = 3,
) -> jax.Array:
    """Synthetic-but-learnable labels: quantile buckets of the summed
    in-degree over every semantic graph ending at the target type.  The
    container has no real label files, and degree buckets correlate with
    topology, so both executors can be trained and compared (the
    convergence claim is relative: banded >= jnp)."""
    deg = np.zeros(num_dst, np.float64)
    for t in targets:
        rel = semantic[t]
        if rel.num_dst == num_dst:
            deg += np.bincount(rel.dst, minlength=num_dst)
    qs = np.quantile(deg, np.linspace(0, 1, num_classes + 1)[1:-1])
    return jnp.asarray(np.digitize(deg, qs).astype(np.int32))


def propagated_feature_labels(
    semantic: Dict[str, Any],
    targets: List[str],
    features: Dict[str, np.ndarray],
    num_dst: int,
    num_classes: int = 3,
    seed: int = 0,
) -> jax.Array:
    """Labels a GNN can *generalize* on: quantile buckets of a random
    linear probe of the mean-aggregated neighbour features.

    ``degree_bucket_labels`` is memorizable but not predictable from the
    (random) synthetic features, so validation accuracy sits at chance;
    this variant plants the signal inside exactly the computation a
    one-layer GFP pass performs (project -> aggregate), making
    convergence-to-accuracy a real claim for both executors.
    """
    rng = np.random.default_rng(seed)
    y_raw = np.zeros(num_dst, np.float64)
    probes: Dict[str, np.ndarray] = {}
    for t in targets:
        rel = semantic[t]
        if rel.num_dst != num_dst:
            continue
        st = t[0]
        x = features.get(st)
        if x is None:  # featureless source type: fall back to degree
            p = np.ones(rel.num_src, np.float64)
        else:
            if st not in probes:
                probes[st] = rng.standard_normal(x.shape[1])
            p = np.asarray(x, np.float64) @ probes[st]
        summed = np.zeros(num_dst, np.float64)
        np.add.at(summed, rel.dst, p[rel.src])
        deg = np.bincount(rel.dst, minlength=num_dst)
        y_raw += summed / np.maximum(deg, 1)
    qs = np.quantile(y_raw, np.linspace(0, 1, num_classes + 1)[1:-1])
    return jnp.asarray(np.digitize(y_raw, qs).astype(np.int32))


def make_train_step(
    model,
    graphs: List[Any],
    *,
    lr: float = 3e-3,
    warmup: int = 20,
    total: int = 200,
    weight_decay: float = 0.0,
    clip_norm: Optional[float] = None,
    kernel_backend: Optional[str] = None,
) -> Callable[..., Tuple[HGNNTrainState, jax.Array]]:
    """Build the jitted train step ``(state, features, labels, mask) ->
    (state, loss)`` for one (model, graphs) pair; the batches pick the NA
    step (all ``SemanticGraphBatch`` or all ``BandedBatch``)."""
    lr_fn = warmup_cosine(lr, warmup=warmup, total=total)

    def step(state: HGNNTrainState, features, labels, mask):
        def loss_fn(p):
            return model.execute_loss(
                p, features, graphs, labels, mask=mask, kernel_backend=kernel_backend
            )

        loss, grads = jax.value_and_grad(loss_fn)(state.params)
        if clip_norm is not None:
            grads, _ = clip_by_global_norm(grads, clip_norm)
        params, opt = adamw_update(
            grads,
            state.opt,
            state.params,
            lr_fn(state.opt.step),
            weight_decay=weight_decay,
        )
        return HGNNTrainState(params=params, opt=opt), loss

    return jax.jit(step)


def make_eval_fn(
    model,
    graphs: List[Any],
    *,
    kernel_backend: Optional[str] = None,
) -> Callable[..., jax.Array]:
    """Jitted masked accuracy ``(params, features, labels, mask) -> ()``."""

    def accuracy(params, features, labels, mask):
        logits = model.execute(params, features, graphs, kernel_backend=kernel_backend)
        hit = (logits.argmax(-1) == labels).astype(jnp.float32)
        return jnp.sum(hit * mask) / jnp.maximum(mask.sum(), 1.0)

    return jax.jit(accuracy)


def fit(
    model,
    graphs: List[Any],
    features,
    labels: jax.Array,
    masks: Dict[str, jax.Array],
    *,
    epochs: int = 100,
    seed: int = 0,
    lr: float = 3e-3,
    weight_decay: float = 0.0,
    kernel_backend: Optional[str] = None,
    epoch_callback: Optional[Callable[[int, float], None]] = None,
    ckpt_dir: Optional[str] = None,
    ckpt_every: int = 1,
) -> Dict[str, Any]:
    """Full-graph training loop; returns final state + metric history.

    One epoch is one full-graph step (the standard semi-supervised
    setting).  ``epoch_callback(epoch, loss)`` lets callers time or log
    per-epoch without re-implementing the loop (``benchmarks/train_bench``
    uses it for the latency trajectory).  Prefer reaching this through
    ``repro.api.CompiledHGNN.fit``, which passes the session's batches
    and kernel backend.

    ``ckpt_dir`` enables fault-tolerant training through
    ``repro.train.checkpoint.CheckpointManager``: train state (params +
    optimizer) is saved atomically every ``ckpt_every`` epochs, and a
    ``fit`` pointed at a directory with checkpoints resumes from the
    latest *complete* one (a crash mid-save leaves only a ``.tmp-`` dir,
    which restore skips and the next save garbage-collects).  The loss
    history is carried in the checkpoint, so the returned ``losses``
    covers every epoch regardless of how many times the loop restarted.
    """
    state = init_train_state(model, jax.random.key(seed))
    ckpt = None
    start_epoch = 0
    losses: List[float] = []
    if ckpt_dir is not None:
        if ckpt_every < 1:
            raise ValueError(f"ckpt_every must be >= 1, got {ckpt_every}")
        from repro.train.checkpoint import CheckpointManager

        ckpt = CheckpointManager(ckpt_dir)
        restored = ckpt.restore_latest(state)
        if restored is not None:
            _, state, extra = restored
            start_epoch = int(extra["epoch"])
            losses = [float(x) for x in extra.get("losses", [])]
    step = make_train_step(
        model,
        graphs,
        lr=lr,
        warmup=max(1, epochs // 10),
        total=epochs,
        weight_decay=weight_decay,
        kernel_backend=kernel_backend,
    )
    acc_fn = make_eval_fn(model, graphs, kernel_backend=kernel_backend)
    for epoch in range(start_epoch, epochs):
        state, loss = step(state, features, labels, masks["train"])
        losses.append(float(loss))
        if epoch_callback is not None:
            epoch_callback(epoch, losses[-1])
        if ckpt is not None and (epoch + 1) % ckpt_every == 0:
            # extra carries resume state: completed-epoch count + losses
            ckpt.save(epoch + 1, state, extra={"epoch": epoch + 1, "losses": losses})
    return {
        "state": state,
        "losses": losses,
        "train_acc": float(acc_fn(state.params, features, labels, masks["train"])),
        "val_acc": float(acc_fn(state.params, features, labels, masks["val"])),
        "test_acc": float(acc_fn(state.params, features, labels, masks["test"])),
    }

"""Model-market simulation: partition a dataset, locally train each client,
and hand the server nothing but the pre-trained models (+ sizes).

This is the setting of the whole paper — the server-side pipeline
(:mod:`repro.core`) must work from these artifacts alone.
"""
from __future__ import annotations

from functools import partial
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.config.train import OFLConfig, TrainConfig
from repro.core.client_bank import ClientBank, make_ensemble
from repro.core.ensemble import ensemble_logits
from repro.data.partitions import partition_dataset
from repro.fed.client import evaluate_cnn, local_train, local_train_group
from repro.models.cnn import cnn_apply, init_cnn
from repro.utils import get_logger

log = get_logger("market")


def build_market(
    seed: int,
    x: np.ndarray,
    y: np.ndarray,
    cfg: OFLConfig,
    num_classes: int,
    archs: Optional[Sequence[str]] = None,
    local_epochs: Optional[int] = None,
) -> Tuple[List[Callable], List[Any], List[int], List[np.ndarray]]:
    """Returns (client_apply_fns, client_params, shard_sizes, shard_indices).

    ``archs``: one CNN arch id per client (heterogeneous market) or None for
    all-``cnn5``."""
    n = cfg.num_clients
    archs = list(archs) if archs else ["cnn5"] * n
    assert len(archs) == n
    parts = partition_dataset(seed, y, cfg)
    in_shape = x.shape[1:]
    tc = TrainConfig(
        optimizer="sgdm",
        learning_rate=cfg.local_lr,
        momentum=cfg.local_momentum,
        batch_size=cfg.local_batch_size,
        seed=seed,
    )
    applies, params_list, sizes = [], [], []
    epochs = cfg.local_epochs if local_epochs is None else local_epochs
    for k in range(n):
        key = jax.random.fold_in(jax.random.key(seed), k)
        p0 = init_cnn(key, archs[k], num_classes, in_shape)
        xb, yb = x[parts[k]], y[parts[k]]
        pk = local_train(partial(cnn_apply, archs[k]), p0, xb, yb, tc, epochs)
        applies.append(partial(cnn_apply, archs[k]))
        params_list.append(pk)
        sizes.append(len(parts[k]))
        acc = evaluate_cnn(applies[-1], pk, xb[: min(512, len(xb))], yb[: min(512, len(yb))])
        log.info("client %d (%s): shard=%d train-acc=%.3f", k, archs[k], len(parts[k]), acc)
    return applies, params_list, sizes, parts


def build_market_grouped(
    seed: int,
    x: np.ndarray,
    y: np.ndarray,
    cfg: OFLConfig,
    num_classes: int,
    archs: Optional[Sequence[str]] = None,
    local_epochs: Optional[int] = None,
) -> Tuple[ClientBank, Tuple[Any, ...], List[int], List[np.ndarray]]:
    """The grouped-bank twin of :func:`build_market`: same partition, same
    per-client inits and ``batch_iterator`` step sequences, but clients of
    the same arch train as ONE vmapped program per group
    (:func:`repro.fed.client.local_train_group`) instead of K sequential
    loops. Returns ``(bank, bank_params, shard_sizes, shard_indices)`` —
    the bank's params feed the server pipeline directly (its
    ``bank.logits_all`` is the ``logits_all_fn``), or convert back with
    ``bank.unstack_params`` for per-client APIs."""
    n = cfg.num_clients
    archs = list(archs) if archs else ["cnn5"] * n
    assert len(archs) == n
    parts = partition_dataset(seed, y, cfg)
    in_shape = x.shape[1:]
    tc = TrainConfig(
        optimizer="sgdm",
        learning_rate=cfg.local_lr,
        momentum=cfg.local_momentum,
        batch_size=cfg.local_batch_size,
        seed=seed,
    )
    epochs = cfg.local_epochs if local_epochs is None else local_epochs
    applies, inits = [], []
    for k in range(n):
        key = jax.random.fold_in(jax.random.key(seed), k)
        applies.append(partial(cnn_apply, archs[k]))
        inits.append(init_cnn(key, archs[k], num_classes, in_shape))
    bank, bank_params0 = ClientBank.build(applies, inits, scan_chunk=cfg.ensemble_scan_chunk)
    bank_params, at = [], 0
    for g, count in enumerate(bank.counts):
        members = bank.order[at : at + count]
        at += count
        shards = [(x[parts[k]], y[parts[k]]) for k in members]
        trained = local_train_group(bank.applies[g], bank_params0[g], shards, tc, epochs)
        bank_params.append(trained)
        log.info(
            "group %d (%s): %d clients, shards=%s",
            g, archs[members[0]], count, [len(s[0]) for s in shards],
        )
    sizes = [len(parts[k]) for k in range(n)]
    return bank, tuple(bank_params), sizes, parts


def market_eval_fn(
    client_applies: List[Callable],
    client_params: List[Any],
    server_apply: Callable,
    test_x: np.ndarray,
    test_y: np.ndarray,
    batch_size: int = 512,
    impl: str = "grouped",
) -> Callable:
    """Builds eval_fn(server_params, w) -> {server_acc, ensemble_acc}.
    ``server_params=None`` skips the server forward entirely and returns only
    ``ensemble_acc`` (ensemble-only methods like FedENS have no trained
    server — evaluating a random init would be wasted work and a misleading
    number). ``impl`` picks the client-forward engine (grouped ClientBank by
    default; "looped" is the unrolled parity baseline)."""
    logits_all_fn, client_params = make_ensemble(client_applies, client_params, impl=impl)

    # client params are arguments, not closure constants: closed over, all K
    # clients' weights would be baked into every compiled program (tens of
    # MB per batch shape) and crowd everything else out of a size-capped
    # persistent compile cache
    @jax.jit
    def _ens_preds(cp, w, xb):
        la = logits_all_fn(cp, xb)
        return jnp.argmax(ensemble_logits(la, w), axis=-1)

    @jax.jit
    def _batch_preds(cp, server_params, w, xb):
        srv_pred = jnp.argmax(server_apply(server_params, xb), axis=-1)
        return _ens_preds(cp, w, xb), srv_pred

    def eval_fn(server_params, w) -> Dict[str, float]:
        ens_ok = srv_ok = 0
        for i in range(0, len(test_x), batch_size):
            xb = jnp.asarray(test_x[i : i + batch_size])
            if server_params is None:
                ep = _ens_preds(client_params, w, xb)
            else:
                ep, sp = _batch_preds(client_params, server_params, w, xb)
                srv_ok += int((np.asarray(sp) == test_y[i : i + batch_size]).sum())
            ens_ok += int((np.asarray(ep) == test_y[i : i + batch_size]).sum())
        out = {"ensemble_acc": ens_ok / len(test_x)}
        if server_params is not None:
            out["server_acc"] = srv_ok / len(test_x)
        return out

    return eval_fn

"""Federated LoRA fine-tuning of a frozen language model (FedIT,
arXiv:2305.05644; OpenFedLLM, arXiv:2402.06954).

Every client holds a shard of packed token rows and trains low-rank
adapters (``repro.models.lora``) on a frozen base shared by all of them;
only the adapters are the task's parameters (one flat dict, a leaf per
matrix: ``lora.flat``), so only they are uploaded, cached and
aggregated.  A round of client ``k`` is ``steps`` SGD steps
of the next-token loss, one row a step: in round ``t`` step ``j`` reads
row ``(steps * (t - 1) + j) mod n_k`` of its ``n_k`` rows.

The base and the token rows are the task's ``operands()``: the dense SAFA
scan engine hands them to its compiled segment as arguments and on to
``local_train`` as its third argument, so they never become program
constants.  Clients train one after another (``lax.map``), so
activations are held for one sequence at a time.
"""
from __future__ import annotations

import hashlib

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.core.federation import Task
from repro.models import granite, lora


class LoraLMTask(Task):
    """``base``: frozen weights in ``granite``'s layout; ``tokens``
    [rows, seq] int32, client ``k`` holding rows ``offsets[k]`` to
    ``offsets[k] + counts[k]``; ``heldout`` [rows, seq] for
    ``evaluate``."""

    def __init__(self, cfg, base, tokens, offsets, counts, heldout, *,
                 lr: float, steps: int):
        self.cfg = cfg
        self.lr, self.steps = float(lr), int(steps)
        self.m = len(offsets)
        self.seq = int(tokens.shape[1])
        self._host = (np.asarray(tokens), np.asarray(offsets),
                      np.asarray(counts), np.asarray(heldout))
        self._operands = {
            'base': base,
            'tokens': jnp.asarray(tokens, jnp.int32),
            'offsets': jnp.asarray(offsets, jnp.int32),
            'counts': jnp.asarray(counts, jnp.int32),
        }
        self._heldout = jnp.asarray(heldout, jnp.int32)
        self._eval_jit = jax.jit(self._eval)

    def init_global(self, key):
        return lora.flat(granite.init_adapters(key, self.cfg))

    def operands(self):
        return self._operands

    # -- client_update: `steps` SGD steps a round ---------------------------
    def _client(self, base, adapters, rows):
        def step(ad, tokens):
            g = jax.grad(lambda a: granite.loss(base, lora.nest(a),
                                                tokens[None], self.cfg))(ad)
            return jax.tree.map(lambda p, d: p - self.lr * d, ad, g), None
        return jax.lax.scan(step, adapters, rows)[0]

    def _train(self, stacked, clients, round_idx, ops):
        k = clients.shape[0]
        obs.count('lm_local_train',
                  flops=k * self.steps * granite.train_flops(self.cfg,
                                                             self.seq),
                  tokens=k * self.steps * self.seq, clients=k)
        t = (jnp.asarray(round_idx, jnp.int32) - 1) * self.steps \
            + jnp.arange(self.steps, dtype=jnp.int32)
        rows = ops['offsets'][clients][:, None] \
            + t[None, :] % ops['counts'][clients][:, None]
        tokens = ops['tokens'][rows]                        # [k, steps, seq]
        return jax.lax.map(lambda a: self._client(ops['base'], *a),
                           (stacked, tokens))

    def local_train(self, stacked_params, round_idx, operands):
        """``operands``: ``operands()``, as the compiled segment received
        them."""
        return self._train(stacked_params, jnp.arange(self.m), round_idx,
                           operands)

    def local_train_rows(self, params_rows, rows, round_idx, operands):
        """Row for row the computation ``local_train`` does for clients
        ``rows`` (sentinel ids >= m clamp to the last client)."""
        return self._train(params_rows, jnp.minimum(rows, self.m - 1),
                           round_idx, operands)

    # -- evaluation: held-out next-token loss -----------------------------------
    def _eval(self, adapters, base, heldout):
        with obs.scope('eval'):
            adapters = lora.nest(adapters)
            losses = jax.lax.map(
                lambda row: granite.loss(base, adapters, row[None], self.cfg),
                heldout)
            return jnp.mean(losses)

    def evaluate(self, global_params) -> dict:
        loss = self._eval_jit(global_params, self._operands['base'],
                              self._heldout)
        return {'loss': float(loss)}

    def fingerprint(self) -> str:
        """The token shards, the held-out rows, the hyperparameters and
        the model's configuration (the frozen weights are the caller's
        and are not hashed)."""
        if '_fingerprint' not in self.__dict__:
            h = hashlib.sha256()
            for a in self._host:
                h.update(np.ascontiguousarray(a).tobytes())
            h.update(repr((self.lr, self.steps, self.cfg)).encode())
            self._fingerprint = \
                f'{type(self).__name__}:{h.hexdigest()[:16]}'
        return self._fingerprint

"""A toy architecture for the harness's tests: attention layers that keep
keys and values in the paged pool, between state layers that keep a
recurrent state a lane.  Its reference is a running mean of the token
embeddings (the state) read out through the tied embedding table."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from bench.reference import matmul, rms
from bench.work import F32, Work

STATE_BYTES = 1000  # one lane's recurrent state over all state layers


def spec(conf: dict) -> dict:
    return {"vocab": conf["vocab_size"], "norm_eps": 1e-6}


@jax.jit
def _mean_state(table, tokens):
    x = jnp.take(table, tokens, axis=0).astype(jnp.float32)
    return jnp.cumsum(x, axis=0) / jnp.arange(1, x.shape[0] + 1, dtype=jnp.float32)[:, None]


def logits(params, spec: dict, tokens, rows, *, quant=None):
    table = params["embed"]["table"].astype(jnp.float32)
    h = rms(jnp.take(_mean_state(table, tokens), rows, axis=0), 1.0, spec["norm_eps"])
    return matmul(h, table.T, quant)[:, : spec["vocab"]]


def _attention_layers(conf: dict) -> int:
    return conf["layer_types"].count("attention")


def kv_bytes_per_token(conf: dict) -> int:
    return 2 * _attention_layers(conf) * conf["num_key_value_heads"] * conf["head_dim"] * 2


def _weights(conf: dict) -> int:
    return conf["vocab_size"] * conf["hidden_size"] * 2


def _step(conf: dict, call: dict) -> Work:
    if call["kind"] == "decode":
        lanes, rows, keys = len(call["lengths"]), len(call["lengths"]), sum(call["lengths"])
    else:
        lanes, rows, keys = 1, call["n"], call["start"] + call["n"]
    head = 2 * conf["hidden_size"] * conf["vocab_size"] * rows
    return Work(head, _weights(conf) + keys * kv_bytes_per_token(conf)
                + lanes * 2 * STATE_BYTES + rows * conf["vocab_size"] * F32)


def _scan_kernel(conf: dict, call: dict) -> Work:
    """The state layers' scan: a state read and written a lane, one
    multiply-add a state byte a row."""
    lanes, rows = ((len(call["lengths"]),) * 2 if call["kind"] == "decode"
                   else (1, call["n"]))
    return Work(2 * STATE_BYTES * rows, lanes * 2 * STATE_BYTES)


WORK = {
    "decode_step": lambda conf, call: _step(conf, call) if call["kind"] == "decode" else None,
    "chunk_step": lambda conf, call: _step(conf, call) if call["kind"] == "chunk" else None,
    "scan_kernel": _scan_kernel,
}

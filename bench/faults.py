"""Faults planted into a built server, to show that the comparison which
decides ``correct`` catches them: the tests plant them at tiny sizes,
``bench/control.py --fault`` on the chip.  The benchmark's own runs
never plant one.

* ``token_altered``: the greedy pick of a prefill's first token is
  moved to the next vocabulary id.
* ``state_unchanged``: a decode step returns the KV pool it was given,
  its writes dropped (where the pool is not donated: the CPU).
* ``exchange_left_out``: under a ``data`` mesh, each chip's own tokens
  stand for every chip's, with no gather between chips.
* ``half_batch``: the upper half of the decode batch is left out, its
  rows given the lower half's logits.
"""

from __future__ import annotations

FAULTS = ("token_altered", "state_unchanged", "exchange_left_out", "half_batch")


def plant(server, fault: str) -> None:
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    if fault == "token_altered":
        greedy = server._greedy_fn
        vocab = server.cfg.vocab_size
        server._greedy_fn = lambda lg: (greedy(lg) + 1) % vocab
    elif fault == "state_unchanged":
        decode = server._decode_fn

        def decode_unchanged(params, pages, *args):
            logits, _ = decode(params, pages, *args)
            return logits, pages

        server._decode_fn = decode_unchanged
    elif fault == "exchange_left_out":
        n = server.mesh.shape[server.dp_axis]
        local = lambda lg: jnp.tile(jnp.argmax(lg, axis=-1).astype(jnp.int32), n)
        server._greedy_gather_fn = jax.jit(jax.shard_map(local, mesh=server.mesh, in_specs=P(server.dp_axis), out_specs=P(), check_vma=False))
    elif fault == "half_batch":
        decode = server._decode_fn
        half = server.batch // 2
        lower = jax.jit(lambda lg: jnp.concatenate([lg[:half], lg[:half]]))

        def decode_half(params, pages, *args):
            logits, pages = decode(params, pages, *args)
            return lower(logits), pages

        server._decode_fn = decode_half
    else:
        raise ValueError(f"unknown fault {fault!r}; one of {FAULTS}")

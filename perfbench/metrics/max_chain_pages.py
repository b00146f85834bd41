"""table ops: the longest bucket chain, in pages, of the engine's tables as
the measured window closed (``hashmap.chain_lengths``)."""


def read(run):
    if not run.tables_end:
        return None
    import jax.numpy as jnp
    from repro.core import hashmap
    return max(int(jnp.max(hashmap.chain_lengths(hm)))
               for hm in run.tables_end)

"""Builders of the configurations: each puts the program's own model
behind its normal entry, holding weights the benchmark made from the seed."""
from __future__ import annotations


def seed_key(seed: int):
    """A key from any whole number up to 2**63: the low 31 bits, then the
    rest folded in (PRNGKey itself refuses what 32 signed bits do not
    hold)."""
    import jax
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF),
                              seed >> 31)

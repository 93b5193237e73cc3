"""Plain numpy moves between a contiguous ``[S, H, L, ...]`` cache and
page planes ``[N, H, page_len, ...]`` behind an ``[S, P]`` table: what
the tests that compare a paged step with its contiguous reference
(``decode_step_slots``, ``verify_step_slots``, the table-less
``commit_tree_path``) lay their caches out with. Independent of the
code under test (no ``_gather_pages``, no ``_cache_write_pages``)."""

import numpy as np

import jax.numpy as jnp


def scrambled_tables(num_slots: int, pages_per_slot: int, seed: int = 0):
    """Every slot's logical pages on distinct physical pages in a
    shuffled order: ``(tables [S, P] int32, num_pages)``."""
    n = num_slots * pages_per_slot
    perm = np.random.RandomState(seed).permutation(n)
    return perm.reshape(num_slots, pages_per_slot).astype(np.int32), n


def to_pages(cache, tables, page_len: int, num_pages: int):
    """The contiguous cache's rows laid out as pages: logical page
    ``p`` of slot ``s`` lands on physical page ``tables[s, p]``."""
    out = []
    for layer in cache:
        if layer is None:
            out.append(None)
            continue
        entry = {}
        for key, arr in layer.items():
            arr = np.asarray(arr)                       # [S, H, L, ...]
            pool = np.zeros((num_pages, arr.shape[1], page_len)
                            + arr.shape[3:], arr.dtype)
            for s in range(tables.shape[0]):
                for p in range(tables.shape[1]):
                    pool[tables[s, p]] = \
                        arr[s, :, p * page_len:(p + 1) * page_len]
            entry[key] = jnp.asarray(pool)
        out.append(entry)
    return out


def logical_view(paged, tables):
    """The page planes read back in logical order, as numpy
    ``[S, H, P * page_len, ...]`` per key."""
    out = []
    for layer in paged:
        if layer is None:
            out.append(None)
            continue
        entry = {}
        for key, pool in layer.items():
            pg = np.asarray(pool)[tables]           # [S, P, H, pl, ...]
            pg = np.moveaxis(pg, 2, 1)              # [S, H, P, pl, ...]
            entry[key] = pg.reshape(pg.shape[:2] + (-1,) + pg.shape[4:])
        out.append(entry)
    return out


def assert_same_cache(ref, paged, tables, atol: float = 1e-6):
    """The page planes, read back in logical order, hold the contiguous
    reference cache: same keys a layer, same values."""
    for a, b in zip(ref, logical_view(paged, tables)):
        if a is None:
            continue
        assert sorted(a) == sorted(b)
        for key in a:
            np.testing.assert_allclose(b[key], np.asarray(a[key]),
                                       atol=atol)

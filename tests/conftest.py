# NOTE: deliberately NO XLA_FLAGS / device-count override here — smoke tests
# and benches must see the single real CPU device. Sharded tests spawn
# subprocesses with their own XLA_FLAGS (tests/test_sharding.py).
import jax
import jax.numpy as jnp
import numpy as np
import pytest


@pytest.fixture(scope="session")
def rng():
    return jax.random.PRNGKey(42)


#: block-table layouts of the paged attention kernel tests: name -> (page,
#: table width P, pool blocks N, each slot's allocated blocks (the rest of
#: its row is the sentinel N), each slot's base position, VMEM budget for
#: the kernel's page buffers or None). With page 16 a grid step takes 8
#: pages, so P = 11 leaves a last step of 3.
PAGED_LAYOUTS = {
    # the original mix: GQA broadcast and sentinel table entries
    "mixed": (8, 4, 12, [[2, 7, 1], [4, 9], [11]], [17, 9, 3], None),
    # P not a multiple of the pages per step; one slot live to the end
    "ragged_table": (16, 11, 40, [[3, 17, 29, 5, 38, 11, 0, 22, 31, 8, 14],
                                  [6, 1, 33, 27, 19, 2, 36, 12, 25, 9],
                                  [30, 4]], [170, 140, 20], None),
    # chunk 1: live context ends exactly on a step's last page (127), on
    # the next step's first page (128), on the table's last cell (175)
    "block_edge": (16, 11, 40, [list(range(8)), list(range(8, 17)),
                                list(range(17, 28))], [127, 128, 175],
                   None),
    # chunk 8: queries cross a page (12..19) and a step's end (124..131)
    "chunk_cross": (16, 11, 40, [[5, 6], list(range(10, 19)),
                                 [21, 22, 23, 24, 25]], [12, 124, 60],
                    None),
    # an idle slot: sentinels only, at position 0
    "idle_slot": (8, 4, 12, [[2, 7, 1], [], [11]], [17, 0, 3], None),
    # a budget for one kv head a step, fewer than the pools hold
    "split_heads": (8, 4, 12, [[2, 7, 1], [4, 9], [11]], [17, 9, 3], 1),
    # the verifier's chunk, one row at the drafter's out-of-table position
    "spec_verify": (16, 11, 40, [list(range(11)), list(range(11, 18)),
                                 [39]], [176, 100, 5], None),
}


@pytest.fixture
def paged_layout(monkeypatch):
    """``build(name)`` -> (page, tables, pos, N) of ``PAGED_LAYOUTS[name]``,
    with the kernel's VMEM budget patched where the layout sets one."""
    def build(name):
        from repro.kernels import paged_attention
        page, p_tab, n, rows, pos, budget = PAGED_LAYOUTS[name]
        if budget is not None:
            monkeypatch.setattr(paged_attention, "_KV_VMEM_BUDGET", budget)
        tables = np.full((len(rows), p_tab), n, np.int32)
        for b, row in enumerate(rows):
            tables[b, :len(row)] = row
        return page, jnp.asarray(tables), jnp.asarray(pos, jnp.int32), n
    return build

"""The kernel layer's counter metric, ``kernels.scatter_updates``: the
scatter-add updates of one vector's step on the busiest shard, from
``stats()[tenant]["kernels"]["scatter_updates"]``.  It reads nothing, and
raises nothing, where no tenant reports the counter."""
import types

import pytest

from chip_bench import cells

SCATTER = cells.reader("kernels.scatter_updates")


def _run(after):
    return types.SimpleNamespace(stats_after=after, trace=None, chips=4)


def _counts(*per_shard):
    return {"device_passes": 2, "kernels": {"scatter_updates": list(per_shard)}}


@pytest.mark.parametrize("after, expected", [
    # one shard: cop20k_A_synth's seg pieces
    ({"t": _counts(124927)}, 124927.0),
    # four shards, both passes summed per shard: the busiest one
    ({"t": _counts(31000, 33500, 32900, 31800)}, 33500.0),
    # two tenants on one mesh: summed shard by shard, then the busiest
    ({"a": _counts(10, 40, 5, 0), "b": _counts(35, 0, 20, 0)}, 45.0),
    # an ell program scatters nothing, and says so
    ({"t": _counts(0)}, 0.0),
    # a tenant without the counter (as before it existed) adds nothing
    ({"a": _counts(7, 3), "b": {"device_passes": 1}}, 7.0),
], ids=["one-shard", "busiest-shard", "two-tenants", "ell", "mixed"])
def test_scatter_updates_reads_the_busiest_shard(after, expected):
    assert SCATTER(_run(after)) == expected


@pytest.mark.parametrize("after", [
    {"t": {"device_passes": 1, "exchange": {}}},   # a program before it
    {"t": {"kernels": {}}},                        # no such counter
    {},                                            # no tenant stats at all
], ids=["no-counters", "no-scatter-key", "empty"])
def test_scatter_updates_reads_nothing_without_the_counter(after):
    assert SCATTER(_run(after)) is None

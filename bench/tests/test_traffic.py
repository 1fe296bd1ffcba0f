"""Mixes and their clients are found by name, an open-loop schedule offers
every seed the same work, and a cell on more chips than one is refused
unless its configuration shards the index."""
import numpy as np
import pytest

import run
import traffic

OPEN = traffic.load_module(traffic.HERE + "/kinds", "open_loop")
MIX = {"rate_per_s": 400, "rows": [[1, 3], [4, 1]], "schedule_seed": 9}


def test_every_mix_names_a_client_module():
    import glob
    import os

    mixes = glob.glob(os.path.join(traffic.HERE, "traffic", "*.json"))
    assert mixes
    for path in mixes:
        mix = traffic.load_mix(os.path.basename(path)[:-5])
        assert hasattr(traffic.load_module(
            os.path.join(traffic.HERE, "kinds"), mix["kind"]), "Client")


def test_an_unknown_kind_is_an_error():
    with pytest.raises(ValueError, match="no_such_kind"):
        traffic.make_client({}, {"kind": "no_such_kind"}, None, None, 1)


@pytest.mark.parametrize("on_off_s", [None, [0.5, 1.5]])
def test_open_loop_schedule_is_one_multiset_in_each_seed_order(on_off_s):
    mix = dict(MIX, **({"on_off_s": on_off_s} if on_off_s else {}))
    a = OPEN.schedule(mix, 10.0, 2**33 + 5, 64)
    b = OPEN.schedule(mix, 10.0, 12, 64)
    assert len(a[0]) == len(b[0]) == 4000
    assert sorted(a[1]) == sorted(b[1]) and not np.array_equal(a[1], b[1])
    for due, sizes, starts in (a, b):
        assert np.all(np.diff(due) >= 0) and 0 <= due[0] and due[-1] < 10.0
        assert set(sizes) <= {1, 4} and np.all(starts + sizes <= 64)
        if on_off_s:
            # every arrival falls in an on-period, and all of them are used
            assert np.all(due % 2.0 < 0.5)
            assert len(np.unique(due // 2.0)) == 5


def test_single_row_mix_sends_one_row_per_request():
    mix = traffic.load_mix("served_poisson")
    due, sizes, _ = OPEN.schedule(mix, 2.0, 3, mix["query_pool_rows"])
    assert np.all(sizes == 1)
    assert len(due) == round(mix["rate_per_s"] * 2.0)


def test_a_cell_on_more_chips_than_one_is_refused():
    # a configuration that does not shard holds its index on one chip
    with pytest.raises(run.NoChip, match="holds the index on one"):
        run.check_device({"name": "x.sharded4", "config": "x", "chips": 4},
                         {"name": "x", "backend": "auto"}, False)

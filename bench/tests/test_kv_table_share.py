"""``kv_table_share`` reads the block-table width a paged serve ran with
over its pool, and nothing from a program that does not report them."""
import types

import tiny  # noqa: F401  (puts bench/ and src/ on the path)

import run as R


def _read(*reports):
    return R._load_metric("kv_table_share").read(
        types.SimpleNamespace(reports=list(reports)))


def test_share_of_a_quarter_pool():
    rep = types.SimpleNamespace(table_blocks=80, pool_blocks=320)
    assert _read(rep) == 25.0
    assert abs(_read(types.SimpleNamespace(table_blocks=132, pool_blocks=532))
               - 100 * 132 / 532) < 1e-12


def test_nothing_without_the_counters():
    assert _read(types.SimpleNamespace(decode_rounds=10)) is None
    # a dense run's report carries the fields unset
    assert _read(types.SimpleNamespace(table_blocks=None, pool_blocks=None)) is None
    assert _read() is None

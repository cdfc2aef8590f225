"""TPC-DS star-schema data for the benchmark, made on the host from a seed.

A copy of ``relational/tpcds.py``'s generator kept with the benchmark, so
that a change to the program cannot change the yardstick, sized by a
configuration file (``chipbench/configs/<name>.json``): the spec's row
counts, a 73,049-day ``date_dim`` on the real calendar, and sales dates
in the spec's five sales years.

Dimension attributes that the queries filter on are spread evenly and
in a fixed pattern (row *i* of ``item`` has category *i* mod 10, store
*i* is in state *i* mod 8, and so on), so every seed gives the same
sizes; the seed draws every other value and which dimension rows each
sale references.
"""
from __future__ import annotations

from typing import Dict

import numpy as np

# (name, kind, width): the columns each table keeps, in schema order
COLUMNS = {
    "store_sales": (
        ("ss_sold_date_sk", "i32"), ("ss_item_sk", "i32"),
        ("ss_customer_sk", "i32"), ("ss_store_sk", "i32"),
        ("ss_quantity", "i32"), ("ss_wholesale_cost", "f32"),
        ("ss_list_price", "f32"), ("ss_sales_price", "f32"),
        ("ss_ext_sales_price", "f32"), ("ss_net_profit", "f32")),
    "item": (
        ("i_item_sk", "i32"), ("i_brand_id", "i32"),
        ("i_category_id", "i32"), ("i_category", "str12"),
        ("i_current_price", "f32"), ("i_manager_id", "i32")),
    "customer": (
        ("c_customer_sk", "i32"), ("c_birth_year", "i32"),
        ("c_birth_month", "i32"), ("c_gender", "str4"),
        ("c_preferred", "str4")),
    "store": (
        ("s_store_sk", "i32"), ("s_state", "str4"),
        ("s_number_employees", "i32"), ("s_floor_space", "i32")),
    "date_dim": (
        ("d_date_sk", "i32"), ("d_year", "i32"), ("d_moy", "i32"),
        ("d_dow", "i32")),
}

Catalog = Dict[str, Dict[str, np.ndarray]]


def str_width(kind: str) -> int:
    return int(kind[3:])


def pad_strings(values, width: int) -> np.ndarray:
    """(len(values), width) uint8, zero-padded: the engine's STR layout."""
    out = np.zeros((len(values), width), np.uint8)
    for i, v in enumerate(values):
        b = v.encode() if isinstance(v, str) else v
        out[i, : len(b[:width])] = np.frombuffer(b[:width], np.uint8)
    return out


def _spread(n: int, k: int) -> np.ndarray:
    """Row i gets label i mod k: each label n // k or n // k + 1 times."""
    return (np.arange(n) % k).astype(np.int32)


def _day(s: str) -> np.datetime64:
    return np.datetime64(s, "D")


def generate(config: dict, seed: int) -> Catalog:
    """Host columns of every table, from the configuration and ``seed``.

    The attributes the queries filter on are spread evenly:
    ``i_category`` (and its id), ``s_state``, ``c_gender`` and
    ``c_birth_year``.  Sales reference dimension keys uniformly.
    """
    rng = np.random.default_rng([seed, 0x7DC5])
    rows = {t: int(v["rows"]) for t, v in config["tables"].items()}
    cats = config["categories"]
    states = config["states"]
    y_lo, y_hi = config["birth_years"]

    n_item = rows["item"]
    cat = _spread(n_item, len(cats))
    item = {
        "i_item_sk": np.arange(n_item, dtype=np.int32),
        "i_brand_id": rng.integers(1, config["brands"] + 1, n_item,
                                   dtype=np.int32),
        "i_category_id": cat + 1,
        "i_category": pad_strings(cats, 12)[cat],
        "i_current_price": rng.random(n_item, np.float32) * 100,
        "i_manager_id": rng.integers(1, 50, n_item, dtype=np.int32),
    }
    n_cust = rows["customer"]
    customer = {
        "c_customer_sk": np.arange(n_cust, dtype=np.int32),
        "c_birth_year": y_lo + _spread(n_cust, y_hi - y_lo + 1),
        "c_birth_month": rng.integers(1, 13, n_cust, dtype=np.int32),
        "c_gender": pad_strings(["F", "M"], 4)[_spread(n_cust, 2)],
        "c_preferred": pad_strings(["Y", "N"], 4)[
            rng.integers(0, 2, n_cust)],
    }
    n_store = rows["store"]
    store = {
        "s_store_sk": np.arange(n_store, dtype=np.int32),
        "s_state": pad_strings(states, 4)[_spread(n_store, len(states))],
        "s_number_employees": rng.integers(50, 1000, n_store,
                                           dtype=np.int32),
        "s_floor_space": rng.integers(1000, 100000, n_store,
                                      dtype=np.int32),
    }
    n_date = rows["date_dim"]
    days = _day(config["date_dim_first_day"]) + np.arange(n_date)
    years = days.astype("datetime64[Y]")
    date_dim = {
        "d_date_sk": np.arange(n_date, dtype=np.int32),
        "d_year": (years.astype(np.int64) + 1970).astype(np.int32),
        "d_moy": (days.astype("datetime64[M]").astype(np.int64) % 12
                  + 1).astype(np.int32),
        # 1970-01-01 was a Thursday; 0 = Sunday as in TPC-DS d_dow
        "d_dow": ((days.astype(np.int64) + 4) % 7).astype(np.int32),
    }
    day0 = _day(config["date_dim_first_day"])
    first = int((_day(config["sales_first_day"]) - day0).astype(np.int64))
    last = int((_day(config["sales_last_day"]) - day0).astype(np.int64))

    n = rows["store_sales"]
    wholesale = rng.random(n, np.float32) * np.float32(80)
    list_price = wholesale * (np.float32(1.2) + rng.random(n, np.float32))
    sales_price = list_price * (np.float32(0.5) + np.float32(0.5)
                                * rng.random(n, np.float32))
    qty = rng.integers(1, 100, n, dtype=np.int32)
    store_sales = {
        "ss_sold_date_sk": rng.integers(first, last + 1, n, dtype=np.int32),
        "ss_item_sk": rng.integers(0, n_item, n, dtype=np.int32),
        "ss_customer_sk": rng.integers(0, n_cust, n, dtype=np.int32),
        "ss_store_sk": rng.integers(0, n_store, n, dtype=np.int32),
        "ss_quantity": qty,
        "ss_wholesale_cost": wholesale,
        "ss_list_price": list_price,
        "ss_sales_price": sales_price,
        # f32 * i32 promotes to f64 in numpy: cast back so the host
        # columns hold exactly the values the device sees
        "ss_ext_sales_price": (sales_price * qty).astype(np.float32),
        "ss_net_profit": ((sales_price - wholesale) * qty
                          ).astype(np.float32),
    }
    catalog = {"store_sales": store_sales, "item": item,
               "customer": customer, "store": store, "date_dim": date_dim}
    return catalog


def table_rows(catalog: Catalog, table: str) -> int:
    return len(next(iter(catalog[table].values())))


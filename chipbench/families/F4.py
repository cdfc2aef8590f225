"""F4: store performance for one state (store_sales joined with the
state's stores, grouped by store: profit and volume)."""
from chipbench.reference import group_by, str_eq


def build(t, c, p):
    return (t["store_sales"]
            .join(t["store"].where(c.s_state == p["state"].encode()),
                  "ss_store_sk", "s_store_sk")
            .group_by("s_store_sk")
            .agg(("profit", "sum", "ss_net_profit"),
                 ("vol", "sum", "ss_quantity")))


def reference(ref, p):
    ok = str_eq(ref.t["store"]["s_state"], p["state"])[ref.ss("ss_store_sk")]
    return group_by(ref.ss("ss_store_sk")[ok], "s_store_sk",
                    {"profit": ref.ss("ss_net_profit")[ok],
                     "vol": ref.ss("ss_quantity")[ok]})

"""F2: high-value sales scans (price over a threshold) and loss-leader
scans (price under wholesale cost), with a minimum quantity; selected
rows are returned whole (projection-heavy, no aggregate)."""
import numpy as np

from chipbench.reference import Answer

OUT = ("ss_item_sk", "ss_customer_sk", "ss_sales_price", "ss_net_profit")


def build(t, c, p):
    ss = t["store_sales"]
    if p["kind"] == "loss":
        pred = c.ss_sales_price < c.ss_wholesale_cost
    else:
        pred = c.ss_sales_price > float(p["threshold"])
    return ss.where(pred & (c.ss_quantity >= int(p["min_qty"]))).select(*OUT)


def reference(ref, p):
    price = ref.ss("ss_sales_price")
    if p["kind"] == "loss":
        ok = price < ref.ss("ss_wholesale_cost")
    else:
        ok = price > np.float32(p["threshold"])
    ok &= ref.ss("ss_quantity") >= int(p["min_qty"])
    return Answer({n: ref.ss(n)[ok] for n in OUT})

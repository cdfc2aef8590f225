"""F5: the 100 most profitable sales above a profit floor (filter,
projection, sort and limit)."""
import numpy as np

from chipbench.reference import Answer

LIMIT = 100


def build(t, c, p):
    return (t["store_sales"].where(c.ss_net_profit > float(p["profit_above"]))
            .select("ss_item_sk", "ss_net_profit")
            .sort("ss_net_profit", desc=True)
            .limit(LIMIT))


def reference(ref, p):
    profit = ref.ss("ss_net_profit")
    ok = profit > np.float32(p["profit_above"])
    item, prof = ref.ss("ss_item_sk")[ok], profit[ok]
    if len(prof) <= LIMIT:
        return Answer({"ss_item_sk": item, "ss_net_profit": prof})
    cut = np.partition(prof, len(prof) - LIMIT)[len(prof) - LIMIT]
    above = prof > cut
    at = prof == cut
    # rows strictly above the cut are fixed; the rest of the 100 are any
    # of the rows that tie at the cut
    return Answer({"ss_item_sk": item[above], "ss_net_profit": prof[above]},
                  ties=("ss_net_profit", float(cut),
                        {"ss_item_sk": item[at], "ss_net_profit": prof[at]},
                        LIMIT - int(above.sum())))

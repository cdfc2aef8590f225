"""F6: revenue per item category in one month of one year (store_sales
joined with date_dim and item, grouped by category id)."""
from chipbench.reference import group_by


def build(t, c, p):
    dd = t["date_dim"].where((c.d_year == int(p["year"]))
                             & (c.d_moy == int(p["month"])))
    return (t["store_sales"].join(dd, "ss_sold_date_sk", "d_date_sk")
            .join(t["item"], "ss_item_sk", "i_item_sk")
            .group_by("i_category_id")
            .agg(("rev", "sum", "ss_ext_sales_price")))


def reference(ref, p):
    dd = ref.t["date_dim"]
    ok = ((dd["d_year"] == int(p["year"])) & (dd["d_moy"] == int(p["month"]))
          )[ref.ss("ss_sold_date_sk")]
    cat = ref.per_sale("item", "i_category_id", "ss_item_sk")
    return group_by(cat[ok], "i_category_id",
                    {"rev": ref.ss("ss_ext_sales_price")[ok]})

"""F1: category sales report for a year.

store_sales joined with item (one category) and date_dim (one year),
grouped by brand: total sales and row count."""
from chipbench.reference import group_by, str_eq


def build(t, c, p):
    return (t["store_sales"]
            .join(t["item"].where(c.i_category == p["category"].encode()),
                  "ss_item_sk", "i_item_sk")
            .join(t["date_dim"].where(c.d_year == int(p["year"])),
                  "ss_sold_date_sk", "d_date_sk")
            .group_by("i_brand_id")
            .agg(("total_sales", "sum", "ss_ext_sales_price"),
                 ("n", "count", "")))


def reference(ref, p):
    it, dd = ref.t["item"], ref.t["date_dim"]
    ok = (str_eq(it["i_category"], p["category"])[ref.ss("ss_item_sk")]
          & (dd["d_year"] == int(p["year"]))[ref.ss("ss_sold_date_sk")])
    brand = ref.per_sale("item", "i_brand_id", "ss_item_sk")
    return group_by(brand[ok], "i_brand_id",
                    {"total_sales": ref.ss("ss_ext_sales_price")[ok]},
                    counts="n")



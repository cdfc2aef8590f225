"""F3: customer spend per birth year for one gender and birth cohort
(store_sales joined with a filtered customer, grouped by birth year)."""
from chipbench.reference import group_by, str_eq


def build(t, c, p):
    cu = t["customer"].where((c.c_gender == p["gender"].encode())
                             & (c.c_birth_year >= int(p["born_from"])))
    return (t["store_sales"].join(cu, "ss_customer_sk", "c_customer_sk")
            .group_by("c_birth_year")
            .agg(("spend", "sum", "ss_ext_sales_price")))


def reference(ref, p):
    cu = ref.t["customer"]
    ok = (str_eq(cu["c_gender"], p["gender"])
          & (cu["c_birth_year"] >= int(p["born_from"]))
          )[ref.ss("ss_customer_sk")]
    year = ref.per_sale("customer", "c_birth_year", "ss_customer_sk")
    return group_by(year[ok], "c_birth_year",
                    {"spend": ref.ss("ss_ext_sales_price")[ok]})

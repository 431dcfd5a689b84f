"""TPC-DS query 67, the specification's text whole (``query67.tpl``, DMS =
1200), and its answer as a client shows it.

``benchmark/compare.py`` counts a NULL in the program's answer as a mismatch
whatever the reference holds, and this answer is mostly rolled-up NULLs. So
the 100 rows go through one projection that renders NULL as a client does:
``"<null>"`` for the five string keys, -1 for the three integer keys. The
reference renders the same, so a NULL where a value belongs, or the reverse,
is an exact mismatch.

``benchmark/query_bytes.py`` takes tables and columns from the words of this
file, so beyond the text's own it names no table and no column.
"""

from spark_rapids_tpu import functions as F
# The text groups by nine keys. A program that cannot fold them into a few
# sort operands compiles a 20-operand comparator sort for each capacity the
# aggregate takes: cold, that set-up had not ended after 38 minutes on a v5e
# (PERF.md section 6, PR 35), far longer than a run has. Such a program fails
# here, at once and with an ImportError, and does not hang the run.
from spark_rapids_tpu.ops.sorting import fold_keys  # noqa: F401

TEXT = """\
select * from (
  select i_category, i_class, i_brand, i_product_name, d_year, d_qoy, d_moy, s_store_id, sumsales,
         rank() over (partition by i_category order by sumsales desc) rk
  from (select i_category, i_class, i_brand, i_product_name, d_year, d_qoy, d_moy, s_store_id,
               sum(coalesce(ss_sales_price * ss_quantity, 0)) sumsales
        from store_sales, date_dim, store, item
        where ss_sold_date_sk = d_date_sk and ss_item_sk = i_item_sk and ss_store_sk = s_store_sk
          and d_month_seq between 1200 and 1200 + 11
        group by rollup(i_category, i_class, i_brand, i_product_name, d_year, d_qoy, d_moy, s_store_id)) dw1) dw2
where rk <= 100
order by i_category, i_class, i_brand, i_product_name, d_year, d_qoy, d_moy, s_store_id, sumsales, rk
limit 100
"""

STRING_KEYS = ["i_category", "i_class", "i_brand", "i_product_name"]
INT_KEYS = ["d_year", "d_qoy", "d_moy"]


def dataframe(views):
    session = views["store_sales"].session
    shown = ([F.coalesce(F.col(c), F.lit("<null>")).alias(c)
              for c in STRING_KEYS]
             + [F.coalesce(F.col(c), F.lit(-1)).alias(c) for c in INT_KEYS]
             + [F.coalesce(F.col("s_store_id"), F.lit("<null>"))
                .alias("s_store_id"), F.col("sumsales"), F.col("rk")])
    return session.sql(TEXT).select(*shown)

"""Q1 over a view that the session cached: what a dashboard panel sends.

The first call a session sees caches ``views["lineitem"]`` (``.cache()``, the
tier the configuration's ``spark.rapids.tpu.sql.cache.serializer`` names),
registers the cached DataFrame as the temp view ``lineitem`` of that
DataFrame's session and remembers the session; the set-up's one execution of
the text then fills the cache. Every call returns ``session.sql(TEXT)``, so
every query of the window goes through the SQL front end and reads the cache.

``benchmark/query_bytes.py`` takes tables and columns from the words of this
file, so beyond Q1's own text it names no table and no column.
"""

import weakref

TEXT = """\
select
    l_returnflag,
    l_linestatus,
    sum(l_quantity) as sum_qty,
    sum(l_extendedprice) as sum_base_price,
    sum(l_extendedprice * (1 - l_discount)) as sum_disc_price,
    sum(l_extendedprice * (1 - l_discount) * (1 + l_tax)) as sum_charge,
    avg(l_quantity) as avg_qty,
    avg(l_extendedprice) as avg_price,
    avg(l_discount) as avg_disc,
    count(*) as count_order
from
    lineitem
where
    l_shipdate <= date '1998-12-01' - interval '90' day
group by
    l_returnflag,
    l_linestatus
order by
    l_returnflag,
    l_linestatus
"""

_cached = weakref.WeakSet()   # sessions whose view is the cached one


def dataframe(views):
    session = views["lineitem"].session
    if session not in _cached:
        session.create_or_replace_temp_view("lineitem",
                                            views["lineitem"].cache())
        _cached.add(session)
    return session.sql(TEXT)

"""Query families, one module each: ``build(tables, c, params)`` makes
the query through the program's Relation API, ``reference(ref, params)``
writes its answer with ``chipbench.reference``."""

"""Physical operator correctness vs the numpy oracle, incl. CSV parsing."""
import numpy as np
import pytest

from oracle import execute_oracle, multiset
from repro.relational import (F32, I32, STR, ExecContext, Schema, execute,
                              expr as E, logical as L, make_storage)
from repro.relational.datagen import generate_columns, to_csv_bytes

SCHEMA = Schema.of(("k", I32), ("v", I32), ("x", F32), ("s", STR(8)))


def _toy(nrows=257, seed=0, fmt="columnar"):
    rng = np.random.default_rng(seed)
    cols = {
        "k": rng.integers(0, 20, nrows).astype(np.int32),
        "v": rng.integers(0, 1000, nrows).astype(np.int32),
        "x": rng.random(nrows).astype(np.float32),
        "s": rng.integers(97, 100, (nrows, 8)).astype(np.uint8),
    }
    st, _ = make_storage("t", SCHEMA, nrows, fmt, cols=cols)
    return st, cols


def _run(plan, storages):
    catalog = {st.name: st for st, _ in storages}
    ctx = ExecContext(catalog=catalog)
    table = execute(plan, ctx)
    return table.row_multiset()


def _expect(plan, storages):
    catalog = {}
    for st, cols in storages:
        if st.fmt == "csv":
            # apply the CSV storage truncation (8 fractional digits) so
            # the oracle sees what the engine can possibly read back
            cols = {
                n: (np.floor(a.astype(np.float64) * 1e8) / 1e8
                    ).astype(np.float32) if a.dtype == np.float32 else a
                for n, a in cols.items()
            }
        catalog[st.name] = (st.schema, st.nrows, cols)
    return multiset(execute_oracle(plan, catalog), plan.schema)


@pytest.mark.parametrize("fmt", ["columnar", "csv"])
class TestScanFormats:
    def test_roundtrip(self, fmt):
        st, cols = _toy(fmt=fmt)
        # exact columns round-trip exactly; the f32 column is checked
        # with allclose in TestCSVParse (CSV digit parse has ~1e-7 noise
        # that can flip the multiset's 4-decimal rounding on knife-edge
        # values).
        plan = L.scan("t", SCHEMA, fmt).project("k", "v", "s")
        assert _run(plan, [(st, cols)]) == _expect(plan, [(st, cols)])

    def test_filter(self, fmt):
        st, cols = _toy(fmt=fmt)
        plan = L.scan("t", SCHEMA, fmt).filter(E.cmp("v", ">", 500))
        assert _run(plan, [(st, cols)]) == _expect(plan, [(st, cols)])


class TestOps:
    def setup_method(self):
        self.st, self.cols = _toy()
        self.scan = L.scan("t", SCHEMA, "columnar")
        self.pair = [(self.st, self.cols)]

    def test_filter_compound_predicate(self):
        p = self.scan.filter(E.or_(
            E.and_(E.cmp("v", ">", 800), E.cmp("k", "<=", 10)),
            E.cmp("x", "<", 0.05),
            E.not_(E.cmp("v", "!=", 3)),
        ))
        assert _run(p, self.pair) == _expect(p, self.pair)

    def test_filter_string_eq(self):
        s0 = bytes(self.cols["s"][0].tobytes())
        p = self.scan.filter(E.cmp("s", "==", s0))
        got = _run(p, self.pair)
        assert got == _expect(p, self.pair)
        assert len(got) >= 1

    def test_filter_empty_result(self):
        p = self.scan.filter(E.cmp("v", ">", 10**8))
        assert _run(p, self.pair) == []

    def test_project(self):
        p = self.scan.project("v", "s")
        assert _run(p, self.pair) == _expect(p, self.pair)

    def test_sort_asc_desc(self):
        for desc in (False, True):
            p = self.scan.project("v", "k").sort("v", desc=desc)
            assert _run(p, self.pair) == _expect(p, self.pair)

    def test_limit(self):
        # limit rows are order-dependent; compare row COUNT + containment
        p = self.scan.sort("v").limit(10)
        got = _run(p, self.pair)
        assert len(got) == 10

    def test_union(self):
        a = self.scan.filter(E.cmp("v", ">", 900)).project("k", "v")
        b = self.scan.filter(E.cmp("v", "<", 50)).project("k", "v")
        p = a.union(b)
        assert _run(p, self.pair) == _expect(p, self.pair)

    def test_aggregate_all_fns(self):
        p = self.scan.groupby("k").agg(
            ("n", "count", ""), ("sv", "sum", "v"), ("mn", "min", "v"),
            ("mx", "max", "v"), ("avg", "mean", "x"))
        assert _run(p, self.pair) == _expect(p, self.pair)

    def test_aggregate_multikey(self):
        st2, cols2 = _toy(nrows=300, seed=3)
        p = (L.scan("t", SCHEMA, "columnar")
             .filter(E.cmp("v", "<", 500))
             .groupby("k", "v").agg(("n", "count", "")))
        assert _run(p, [(st2, cols2)]) == _expect(p, [(st2, cols2)])


class TestJoin:
    def _two(self, nl=211, nr=97, dup=True, seed=1):
        rng = np.random.default_rng(seed)
        sl = Schema.of(("a", I32), ("p", I32))
        sr = Schema.of(("b", I32), ("q", I32))
        lcols = {"a": rng.integers(0, 40, nl).astype(np.int32),
                 "p": rng.integers(0, 100, nl).astype(np.int32)}
        hi = 40 if dup else nr
        rcols = {"b": (rng.integers(0, hi, nr).astype(np.int32) if dup
                       else np.arange(nr, dtype=np.int32)),
                 "q": rng.integers(0, 100, nr).astype(np.int32)}
        stl, _ = make_storage("l", sl, nl, "columnar", cols=lcols)
        str_, _ = make_storage("r", sr, nr, "columnar", cols=rcols)
        return (stl, lcols), (str_, rcols), sl, sr

    def test_many_to_many(self):
        (stl, lc), (str_, rc), sl, sr = self._two(dup=True)
        p = L.scan("l", sl).join(L.scan("r", sr), "a", "b")
        assert _run(p, [(stl, lc), (str_, rc)]) == _expect(
            p, [(stl, lc), (str_, rc)])

    def test_fk_join(self):
        (stl, lc), (str_, rc), sl, sr = self._two(dup=False)
        p = L.scan("l", sl).join(L.scan("r", sr), "a", "b")
        assert _run(p, [(stl, lc), (str_, rc)]) == _expect(
            p, [(stl, lc), (str_, rc)])

    def test_join_no_matches(self):
        (stl, lc), (str_, rc), sl, sr = self._two()
        p = (L.scan("l", sl).filter(E.cmp("a", ">", 1000))
             .join(L.scan("r", sr), "a", "b"))
        assert _run(p, [(stl, lc), (str_, rc)]) == []

    def test_wide_build_keys_take_the_binary_probe(self):
        # build keys spread far wider than the probe side: no
        # direct-address table, the searchsorted probe runs
        (stl, lc), (str_, rc), sl, sr = self._two(dup=False)
        rc = dict(rc, b=(rc["b"] * 1_000_003 - 7).astype(np.int32))
        lc = dict(lc, a=rc["b"][lc["a"] % len(rc["b"])])
        stl, _ = make_storage("l", sl, len(lc["a"]), "columnar", cols=lc)
        str_, _ = make_storage("r", sr, len(rc["b"]), "columnar", cols=rc)
        p = L.scan("l", sl).join(L.scan("r", sr), "a", "b")
        got = _run(p, [(stl, lc), (str_, rc)])
        assert got and got == _expect(p, [(stl, lc), (str_, rc)])

    @pytest.mark.parametrize("lo_key,width", [
        (0, 40), (-25, 50), (2**31 - 60, 58), (-2**31, 30)])
    def test_dense_probe_matches_binary_probe(self, lo_key, width):
        import jax.numpy as jnp

        from repro.relational.physical import (I32_SENTINEL, _join_build,
                                               _join_probe,
                                               _join_probe_dense)
        from repro.relational.schema import next_pow2

        rng = np.random.default_rng(width)
        rk = (lo_key + rng.integers(0, width, 64)).astype(np.int32)
        lk = np.concatenate([
            (lo_key + rng.integers(-5, width + 5, 500)).clip(
                -2**31, 2**31 - 1),
            [I32_SENTINEL, -2**31, 2**31 - 1]]).astype(np.int32)
        order, rks, kmin, kmax, _ = _join_build(jnp.asarray(rk),
                                                jnp.int32(60))
        span = next_pow2(int(kmax) - int(kmin) + 1)
        lo1, m1, t1 = _join_probe(jnp.asarray(lk), rks, jnp.int32(490))
        lo2, m2, t2 = _join_probe_dense(jnp.asarray(lk), rks,
                                        jnp.int32(490), kmin, kmax,
                                        span=span)
        m1, m2 = np.asarray(m1), np.asarray(m2)
        np.testing.assert_array_equal(m1, m2)
        hit = m1 > 0
        assert hit.any() and int(t1) == int(t2)
        np.testing.assert_array_equal(np.asarray(lo1)[hit],
                                      np.asarray(lo2)[hit])

    @pytest.mark.parametrize("case", ["fk", "no_matches", "stale_padding"])
    @pytest.mark.parametrize("carry", [False, True])
    def test_unique_join_matches_the_expand_path(self, case, carry):
        # the gather of matched probe rows gives the rows the
        # jnp.repeat expansion gives, in the same order
        import jax.numpy as jnp

        from repro.relational.physical import (_join_build, _join_expand,
                                               _join_gather_unique,
                                               _join_probe, _select_rows)
        from repro.relational.schema import next_pow2

        rng = np.random.default_rng(7)
        nl, nr, lcap, rcap = 211, 97, 256, 128
        rk = rng.permutation(1000)[:rcap].astype(np.int32)
        lk = rk[rng.integers(0, rcap, lcap)]     # padding keys too
        if case == "no_matches":
            lk = lk + 5000
        if case == "stale_padding":
            rk[nr:] = rk[:rcap - nr]             # padding repeats live keys
        lk, lv = jnp.asarray(lk), jnp.asarray(rng.random(lcap, np.float32))
        rv = rng.integers(0, 100, rcap).astype(np.int32)
        order, rks, _, _, dup = _join_build(jnp.asarray(rk), jnp.int32(nr))
        assert not bool(dup)
        lo, m, total = _join_probe(lk, rks, jnp.int32(nl))
        total = int(total)
        assert (total == 0) == (case == "no_matches")
        cap = next_pow2(max(total, 1))
        li, ri = _join_expand(lo, m, cap)
        want = [jnp.take(lk, li), jnp.take(lv, li),
                jnp.take(jnp.take(rk, order), ri),
                jnp.take(jnp.take(rv, order), ri)]
        keys, carried = _select_rows(m, lo, lk, lv, carry=carry)
        got = _join_gather_unique(keys, carried, order, cap, 3,
                                  lo, lk, lv, rk, rv)
        for a, b in zip(got, want):
            assert a.dtype == b.dtype
            np.testing.assert_array_equal(np.asarray(a)[:total],
                                          np.asarray(b)[:total])

    @pytest.mark.parametrize("dup,path", [(False, "unique"),
                                          (True, "expand")])
    def test_join_path_counts(self, dup, path):
        # build-key uniqueness picks the path; duplicates keep the
        # jnp.repeat expansion, and both answer as the oracle does
        from repro.relational import Telemetry

        (stl, lc), (str_, rc), sl, sr = self._two(dup=dup)
        p = L.scan("l", sl).join(L.scan("r", sr), "a", "b")
        storages = [(stl, lc), (str_, rc)]
        ctx = ExecContext(catalog={st.name: st for st, _ in storages})
        got = execute(p, ctx).row_multiset()
        assert got and got == _expect(p, storages)
        assert ctx.metrics.joins == {path: 1}
        tel = Telemetry()
        tel.absorb_exec_metrics(ctx.metrics)
        reg = tel.registry
        assert reg.value("exec.joins") == 1
        assert reg.value("exec.joins", labels={"path": path}) == 1
        assert [(lab["path"], reg.value("exec.joins", labels=lab))
                for lab, _ in reg.series("exec.joins")] == [
            ("unique", int(not dup)), ("expand", int(dup))]

    def test_join_after_filters_with_stale_padding(self):
        # regression: compaction slack rows must never match (the
        # searchsorted sentinel bug)
        (stl, lc), (str_, rc), sl, sr = self._two(nl=300, nr=100)
        p = (L.scan("l", sl).filter(E.cmp("p", ">", 50))
             .join(L.scan("r", sr).filter(E.cmp("q", "<", 50)), "a", "b"))
        assert _run(p, [(stl, lc), (str_, rc)]) == _expect(
            p, [(stl, lc), (str_, rc)])


class TestCompaction:
    @pytest.mark.parametrize("n", [5, 1024, 3000, 4096, 1 << 14])
    @pytest.mark.parametrize("density", [0.0, 0.3, 1.0])
    @pytest.mark.parametrize("slack", [-7, 0, 9])
    @pytest.mark.parametrize("carry", [False, True])
    def test_nonzero_rows_matches_jnp_nonzero(self, n, density, slack,
                                              carry):
        # the compaction of the row index column is the selected rows,
        # 0 after them; sizes below, at and above the selected count;
        # mask lengths that are and are not powers of two
        import jax.numpy as jnp

        from repro.relational.physical import _select_rows, _take_selected

        rng = np.random.default_rng(n + int(density * 10))
        mask = jnp.asarray(rng.random(n) < density)
        size = max(1, int(mask.sum()) + slack)
        (want,) = jnp.nonzero(mask, size=size, fill_value=0)
        rows = jnp.arange(n, dtype=jnp.int32)
        keys, carried = _select_rows(mask, rows, carry=carry)
        (got,) = _take_selected(keys, carried, size, rows)
        assert got.dtype == jnp.int32
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


    @staticmethod
    def _scatter_rows(mask, size):
        """The selection as a scatter of each set row's index to its
        rank (the form the sort replaced): the reference."""
        import jax.numpy as jnp

        rank = jnp.where(mask, jnp.cumsum(mask.astype(jnp.int32)) - 1, size)
        rows = jnp.arange(mask.shape[0], dtype=jnp.int32)
        return jnp.zeros((size,), jnp.int32).at[rank].set(rows, mode="drop")

    @staticmethod
    def _mask(n, kind):
        rng = np.random.default_rng(n)
        return {"random": rng.random(n) < 0.4, "none": np.zeros(n, bool),
                "all": np.ones(n, bool)}[kind]

    @pytest.mark.parametrize("n", [5, 1000, 4096, 1 << 14])
    @pytest.mark.parametrize("kind", ["random", "none", "all"])
    @pytest.mark.parametrize("size", ["under", "exact", "over", "beyond"])
    @pytest.mark.parametrize("carry", [False, True])
    def test_sort_selection_matches_the_scatter_form(self, n, kind, size,
                                                     carry):
        # new_cap below and above the selected count, and beyond the
        # mask's length (a union's capacity can round past it); the
        # columns gathered through the keys or carried through the sort
        import jax.numpy as jnp

        from repro.relational.physical import _select_rows, _take_selected

        mask = jnp.asarray(self._mask(n, kind))
        count = int(mask.sum())
        cap = {"under": max(1, count // 2), "exact": max(1, count),
               "over": count + 9, "beyond": n + 3}[size]
        want = np.asarray(self._scatter_rows(mask, cap))
        assert want.shape == (cap,) and not want[count:].any()
        cols = (jnp.arange(n, dtype=jnp.int32),          # the rows
                jnp.arange(n, dtype=jnp.float32) * 1.5 - 7.0,
                jnp.arange(4 * n, dtype=jnp.uint8).reshape(n, 4))
        keys, carried = _select_rows(mask, *cols, carry=carry)
        assert len(carried) == (2 if carry else 0)
        for taken, col in zip(_take_selected(keys, carried, cap, *cols),
                              cols):
            assert taken.dtype == col.dtype
            np.testing.assert_array_equal(np.asarray(taken),
                                          np.asarray(col)[want])

    @pytest.mark.parametrize("fn", ["compact_nz", "fused_fn", "union_fn"])
    @pytest.mark.parametrize("cap", [8, 64, 1024])
    def test_compacted_outputs_match_the_scatter_form(self, fn, cap):
        # every output buffer, fill rows included, is what the scatter
        # selection gives; caps below and above n/16, where the sort
        # starts carrying the columns
        import jax.numpy as jnp

        from repro.relational.physical import (_compact_nz, _fused_fn,
                                               _union_fn)

        n = 1024
        rng = np.random.default_rng(cap)
        x = jnp.asarray(rng.random(n, dtype=np.float32))
        q = jnp.asarray(rng.integers(0, 100, n).astype(np.int32))
        if fn == "union_fn":
            nl, nr = 300, 200
            mask = jnp.concatenate([jnp.arange(n) < nl, jnp.arange(n) < nr])
            want = [jnp.take(jnp.concatenate([c, c[::-1]]),
                             self._scatter_rows(mask, cap))
                    for c in (x, q)]
            got = _union_fn(None, ("x", "q"), n, n, cap)(
                jnp.int32(nl), jnp.int32(nr), x, q, x[::-1], q[::-1])
        else:
            mask = (x > 0.7) & (q >= 10) & (jnp.arange(n) < 1000)
            want = [jnp.take(c, self._scatter_rows(mask, cap))
                    for c in (x, q)]
            if fn == "compact_nz":
                got = _compact_nz(mask, cap, x, q)
            else:
                pred = E.and_(E.cmp("x", ">", 0.7), E.cmp("q", ">=", 10))
                _, count, _, _, got = _fused_fn(None, pred, ("x", "q"),
                                                ("x", "q"), cap)(
                    jnp.int32(1000), x, q)
                assert int(count) == int(mask.sum())
        for a, b in zip(got, want):
            assert a.dtype == b.dtype
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


class TestCSVParse:
    def test_csv_int_parse_exact(self):
        rng = np.random.default_rng(0)
        vals = np.concatenate([
            np.array([0, 1, 999_999_999], np.int32),
            rng.integers(0, 10**9, 61).astype(np.int32)])
        schema = Schema.of(("v", I32))
        csv = to_csv_bytes(schema, {"v": vals}, len(vals))
        st = __import__("repro.relational.physical", fromlist=["TableStorage"]
                        ).TableStorage("t", schema, len(vals), "csv",
                                       csv_bytes=csv)
        ctx = ExecContext(catalog={"t": st})
        out = execute(L.scan("t", schema, "csv"), ctx)
        np.testing.assert_array_equal(
            np.asarray(out.columns["v"])[: len(vals)], vals)

    def test_csv_float_parse_close(self):
        rng = np.random.default_rng(0)
        vals = rng.random(64).astype(np.float32)
        schema = Schema.of(("x", F32))
        csv = to_csv_bytes(schema, {"x": vals}, len(vals))
        from repro.relational.physical import TableStorage

        st = TableStorage("t", schema, len(vals), "csv", csv_bytes=csv)
        ctx = ExecContext(catalog={"t": st})
        out = execute(L.scan("t", schema, "csv"), ctx)
        np.testing.assert_allclose(
            np.asarray(out.columns["x"])[: len(vals)], vals, atol=1e-6)


class TestPallasFilterPath:
    """The engine's kernel-accelerated filter must agree with XLA."""

    def test_numeric_predicates_match(self):
        st, cols = _toy(nrows=1500, seed=5)
        plan = (L.scan("t", SCHEMA, "columnar")
                .filter(E.or_(E.and_(E.cmp("v", ">", 300),
                                     E.cmp("k", "<=", 15)),
                              E.cmp("x", "<", 0.1)))
                .project("k", "v"))
        ctx_x = ExecContext(catalog={"t": st})
        ctx_p = ExecContext(catalog={"t": st}, use_pallas_filter=True)
        a = execute(plan, ctx_x).row_multiset()
        b = execute(plan, ctx_p).row_multiset()
        assert a == b and len(a) > 0

    def test_string_predicate_falls_back(self):
        st, cols = _toy(nrows=300, seed=6)
        s0 = bytes(cols["s"][0].tobytes())
        plan = L.scan("t", SCHEMA, "columnar").filter(
            E.cmp("s", "==", s0))
        ctx_p = ExecContext(catalog={"t": st}, use_pallas_filter=True)
        ctx_x = ExecContext(catalog={"t": st})
        assert (execute(plan, ctx_p).row_multiset()
                == execute(plan, ctx_x).row_multiset())

"""Control: a selection planned with the paper's one-pass approximate
ranges and refined nothing on the host, the cheaper superset plan of
§V-C.  The first column's range is ``approximate_range``'s include and
exclude passes; each other column keeps only the lower-bound exclusion of
its approximate range, since a plan cannot AND a second include."""
import contextlib

from bench.control import patched

KIND = "control"


@contextlib.contextmanager
def apply():
    from repro.core.bitweaving import RowCodec
    from repro.core.range_query import RangePlan, approximate_range

    def where(self, predicates):
        named = [c for c in self.columns if c.name in predicates]
        include, exclude = (), []
        for i, c in enumerate(named):
            lo, hi = predicates[c.name]
            plan = approximate_range(lo, hi, shift=self.shifts[c.name],
                                     width=c.width)
            if i == 0:
                include = plan.include
            exclude += plan.exclude
        return RangePlan(include=include, exclude=tuple(exclude), exact=False)
    with patched(RowCodec, "where", where):
        yield None

"""scale * (the `num` series as the scrape after the window shows them), over
the `den` series there where the file gives any: a gauge's value, or the
ratio of two. Series are named as in prom_ratio. A level is read and not a
growth: what a gauge holds at the end of the window is the reading.

A gauge that reads 0 was never set (a backend without the statistic, as the
CPU keeps no memory_stats()) or belongs to a family the program lacks:
nothing read, and nothing refused. So, as prom_ratio_optional does and for
its reason, this reader hands run.py no names; tests/test_benchmark_metrics.py
holds `reads()` to a fresh `observability.Metrics()` instead."""


def reads(args: dict) -> set[str]:
    return {series["name"] for series in args["num"] + args.get("den", [])}


def read(run, num, den=None, scale=1.0):
    def level(series):
        return sum(run.after.value(s["name"], s.get("labels")) for s in series)

    value, over = level(num), 1.0 if den is None else level(den)
    return scale * value / over if value > 0 and over > 0 else None

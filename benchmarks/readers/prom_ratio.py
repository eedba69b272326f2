"""scale * (growth of the `num` series) / (growth of the `den` series) over
the window, from the scrapes before and after it. A histogram's mean is the
ratio of its `_sum` to its `_count`. Each series is
{"name": sample name, "labels": {label: value, ...}} and stands for the sum
of every series that carries those labels. Nothing counted, nothing read."""


def names(args: dict) -> set[str]:
    """The Prometheus samples read: run.py refuses a run whose daemon does
    not declare them, so that a renamed counter fails and is not left out."""
    return {series["name"] for series in args["num"] + args["den"]}


def read(run, num, den, scale=1.0):
    def growth(series):
        return sum(
            run.after.value(s["name"], s.get("labels"))
            - run.before.value(s["name"], s.get("labels"))
            for s in series
        )

    counted = growth(den)
    return scale * growth(num) / counted if counted > 0 else None

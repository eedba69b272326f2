"""One read of GET /metrics/prometheus, and the difference of two."""

from __future__ import annotations

import urllib.request

SAMPLE_SUFFIXES = ("", "_total", "_sum", "_count", "_bucket")


class Scrape:
    def __init__(self, text: str):
        from prometheus_client.parser import text_string_to_metric_families

        families = list(text_string_to_metric_families(text))
        self.families = {family.name for family in families}
        self.samples = [s for family in families for s in family.samples]

    @classmethod
    def of(cls, port: int, timeout: float = 60.0) -> "Scrape":
        url = f"http://127.0.0.1:{port}/metrics/prometheus"
        with urllib.request.urlopen(url, timeout=timeout) as resp:
            return cls(resp.read().decode())

    def knows(self, sample: str) -> bool:
        """Whether the daemon declares the metric that `sample` belongs to.
        A labelled counter has no series until it first counts, so this asks
        for the family, and not for a sample."""
        return any(
            sample == family + suffix
            for family in self.families for suffix in SAMPLE_SUFFIXES
        )

    def value(self, name: str, labels: dict | None = None) -> float:
        """Sum over the series of sample `name` whose labels include `labels`."""
        want = (labels or {}).items()
        return sum(
            s.value for s in self.samples
            if s.name == name and want <= s.labels.items()
        )

    def by_label(self, name: str, label: str) -> dict:
        return {
            s.labels.get(label, ""): s.value
            for s in self.samples if s.name == name
        }

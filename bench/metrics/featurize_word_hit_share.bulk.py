"""Featurization: the share of the ``featurize`` spans' word-table lookups
(their ``word_hits`` and ``word_misses``: the words of the texts the LRUs
missed) that found the word already in the table, in percent. Reads
nothing where the spans carry no such attributes."""
from bench import splits


def read(run):
    found = [s.attrs for s in splits.named(run.spans, "featurize")
             if "word_hits" in s.attrs and "word_misses" in s.attrs]
    hits = sum(float(a["word_hits"]) for a in found)
    lookups = hits + sum(float(a["word_misses"]) for a in found)
    return 100.0 * hits / lookups if lookups else None

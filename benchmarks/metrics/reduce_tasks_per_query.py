"""Exchange + stages layer: how many reduce tasks a query of an adaptive plan
ran over coalesced ranges of shuffle partitions: the sum of `tasks` over a
query's `aqe` spans (spark/aqe.read_ranges: one per stage whose reads by
partition were coalesced, by Spark 3.0's rule, to the advisory size), median
per query. At Spark's 200 partitions a query that ran one task a partition
would run 200 a stage. Program spans. None where no query recorded an `aqe`
span (a plan without an adaptive root, a program from before the span)."""
import statistics


def read(run):
    counts = []
    for q in run["window"] + run["profiled"]:
        spans = [s for s in q["spans"] or [] if s["kind"] == "aqe"]
        if spans:
            counts.append(sum(s["attrs"]["tasks"] for s in spans))
    return statistics.median(counts) if counts else None

"""Exchange + stages layer, on a mesh: bytes of exchanged rows that went
device -> host -> device on their way to the consumer (`host_bytes`, which the
program writes on every `exchange` span of a mesh stage: 0 on the all_to_all
itself and on a partition handed over on its chip, the bytes moved where a
partition is re-placed through the host or a batch overflows to files), summed
over a query's `exchange` spans, in MB (1e6 bytes), median per query. A query
with a `mesh` exchange span that moved nothing through the host reads 0. None
where no query has a mesh exchange span, or where the program's spans carry
no such counter."""
import statistics


def read(run):
    sums = []
    for q in run["window"] + run["profiled"]:
        attrs = [s.get("attrs", {}) for s in q["spans"] or []
                 if s["kind"] == "exchange"]
        counted = [a["host_bytes"] for a in attrs if "host_bytes" in a]
        if counted and any(a.get("transport") == "mesh" for a in attrs):
            sums.append(sum(counted) / 1e6)
    return statistics.median(sums) if sums else None

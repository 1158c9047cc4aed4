"""Process start to the first timed query: import, chip init, native
library, table generation and parquet write, references, warm-up queries
(compile, or the persistent cache's load). Host clock."""


def read(run):
    return run["setup_s"]

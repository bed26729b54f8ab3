"""Set-up seconds spent loading compiled programs: the program's executable
store (dl4j_compile_cache_load_seconds) + JAX's persistent cache reads.
A run that compiled everything reads 0: every cell reports it in every run."""


def read(ctx):
    store = sum(v for k, v in ctx["setup_counters"].items()
                if k.startswith("dl4j_compile_cache_load_seconds")
                and not k.endswith("_count"))
    jax_reads = sum(d for e, d in ctx["setup_events"]
                    if e.endswith("cache_retrieval_time_sec"))
    return store + jax_reads

"""Seconds of ``deserialize_and_load`` under set-up's resolutions (the
spans ``compile.deserialize``, summed); 0 on a run that compiled
everything, as ``compile.cache_load_s``."""
import startup_reduce

read = startup_reduce.deserialize_s

"""Seconds of ``setup_s`` the program owns: the union of ``startup.import``,
``startup.init`` and the ``fit.call``s that ended before the window. The
rest of ``setup_s`` less ``setup.before_import_s`` is the harness's, and goes
to standard error."""
import startup_reduce

read = startup_reduce.program_s

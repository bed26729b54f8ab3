"""The compiler's own figure for the temporaries of the step program the
window ran: ``temp_bytes`` of its ``compile.resolve`` span
(``memory_analysis()`` of the executable, loaded or compiled)."""
import startup_reduce

read = startup_reduce.step_program_temp_gb

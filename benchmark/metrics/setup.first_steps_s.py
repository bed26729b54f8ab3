"""Seconds from the end of the first dispatch's ``compile.resolve`` to the
end of that group's ``fit.listeners``: the call's own launch and the
device's first K steps, ended by the listeners' reads of the scores."""
import startup_reduce

read = startup_reduce.first_steps_s

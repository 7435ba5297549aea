"""Plain references the benchmark's ``correct`` is decided against.

``rounds`` is a discrete-event loop over the failure-free rounds, written
from the protocol's rules and the paper's network model; ``replay`` holds
numpy recomputations of the client layer and the crash splice.  Nothing
here imports the program, so a change to it cannot move the yardstick.
"""

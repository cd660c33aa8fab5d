"""Plain float64 references, one per problem a configuration states,
``bench/reference/<problem>.py``, found by the configuration's
``"problem"``.

Each takes the instance as the traffic generator made it (points or
masses, never anything the program made) and one answer as the program
returned it, and gives the numbers that decide ``correct`` (``check``;
``NUMBERS`` names them, and a configuration's ``limits`` hold some of
them). They import nothing of the program.
"""

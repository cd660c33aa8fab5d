"""One module per problem a configuration states (its ``"problem"`` key),
``bench/problems/<problem>.py``: ``spec()``, the program's problem spec;
``WANT``, the artifacts the timed call declares; ``answer(view)``, one
lane's fields for the reference beside its cost and duals. The same name
finds the reference (``bench/reference/<problem>.py``) and the phase
loop's bytes (``bench/work/<problem>.py``)."""

"""API-level name of the compiled DFL round's constructor.

The experiment layer (Session, launchers, dry-run spec builders) obtains
its round function as `build_round`; it is `repro.core.make_dfl_round`
itself, so the two take the same arguments.
"""
from repro.core.fedtrain import make_dfl_round

build_round = make_dfl_round

"""One file a model family: how a configuration's own keys become the
program's config dataclass (and, where its layers are not all alike, its
own flop count). Found by the name in the configuration's ``family``
key (``harness/spec.py load_family``); the contract is set out in
``harness/program.py``."""

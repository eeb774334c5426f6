"""One file per model family, chosen by a configuration's ``reference``
key: everything that knows the configuration file's keys and is not the
plain reference (``benchmark/reference/<name>.py``, which imports nothing of
the program): ``validate``, ``build_model``, ``train_flags``, ``token_ids``
and the counts of operations and bytes."""

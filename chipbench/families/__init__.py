"""One module per model family, found by the ``"family"`` key of a
configuration's file (``cells.load_family``).  A family module is the only
place of the benchmark that knows a model family; ``run.py``, ``cells.py``,
``system.py`` and ``correctness.py`` call through these names and name no
task kind, number, checkpoint key or architecture:

``MODEL_KEYS``
    the keys of the configuration's file that are the model's published
    numbers (``config["model"]``, and what a checkpoint's ``config.json``
    starts from).
``write_checkpoints(root, config, seed) -> {task | "tokenizer": directory}``
    weights and tokenizer from the seed, in the form the program's
    ``build_engine`` loads; dtype, file sharding and special tokens are the
    family's business.  ``router_config.yaml``'s ``@CHECKPOINTS@`` is the
    directory above ``"tokenizer"``.
``warm(engine, config, shapes)``
    the warm-up of the cell's shape set (the workload file's ``shapes``)
    through the engine's public calls; it prints its own report.
``ENGINE_CALLS``
    ``{name of a public engine call: (arguments, answers)}``:
    ``arguments(*args, **kwargs) -> (tasks, texts)`` as the call got them,
    ``texts`` being the REQUEST's text (the key a route's answers are kept
    and its spans are joined by); ``answers(tasks, texts, result)`` yields
    ``(text, task, answer)`` for every answer to keep for ``correct``.
``Reference``
    ``from_checkpoints(config, dirs)``; ``outputs(request, shapes, answers,
    precision="highest")`` the plain reference's raw outputs for one
    request of the traffic, ``answers`` being what the program answered
    for it by task (a generative family runs its reference once over the
    prompt with the served tokens; an encoder ignores them);
    ``answers(request, shapes, answers, precision)`` the same in the shape
    of the program's answers (how the lower-precision control stands in the
    program's place).  The reference itself lies under
    ``chipbench/reference/`` and imports nothing of the program.
``compare(config, request, answers, raw) -> parts``
    one request's part of each number as ``{name: (sum, weight)}``;
    ``correctness.merge`` adds them up.
``finish(parts) -> numbers``, ``expected_numbers(config) -> [name]``
    the numbers, and those a run of this configuration must have read;
    each has a limit in ``limits.json`` or the configuration's own.
"""

CONTRACT = ("MODEL_KEYS", "write_checkpoints", "warm", "ENGINE_CALLS",
            "Reference", "compare", "finish", "expected_numbers")

"""Dataset kinds, each a file ``bench/kinds/<kind>.py`` that the harness
finds by a configuration's ``kind`` (``bench.spec.kind``).  A kind file
holds everything that depends on what the dataset is:

* ``load(svc, dataset, data, rows)``: register the dataset with the
  service and load the generated rows (``data`` is the configuration's
  ``data`` group);
* ``host_answer(app, res)``: one request's answer, copied to the host as
  a dict; raises ``ValueError`` for an app it cannot compare;
* ``LIMITS``, and ``compare(rows, answers, control) -> list[Check]``:
  the numbers compared with the kind's plain reference, each beside its
  limit (``bench.checks`` adds ``failed_requests`` and
  ``compared_answers``);
* ``CONTROLS``: the names of the controls ``compare`` computes in the
  program's place;
* ``FAULTS``: fault name -> ``plant()``, which breaks the timed path
  underneath the service and returns the callables that undo it;
* ``KERNELS``: ``repro.kernels.ops`` entry point -> ``take(entry, *args,
  **kw)``, which makes the record of one concrete call; a record's
  ``resolve()`` runs once the window has closed
  (``bench.kernels.KernelRecorder``).
"""

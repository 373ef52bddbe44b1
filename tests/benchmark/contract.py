"""What a configuration's entry in ``BENCHMARK.json`` and its file owe each
other: one function, applied to the real manifest and to ``bench_toy``'s
copy, so that a cut configuration a later PR adds as new files is held to
the same words as the two that are here (``benchmark/README.md``); and the
two things a cell's test file may say of the order of a list in the
manifest, which later PRs append to."""

import re

ENTRY_KEYS = {"name", "source", "file", "reduced", "why"}
#: a width is never cut (the driver refuses it before any run): only depth,
#: experts held, vocabulary rows and the like are
WIDTH = re.compile(r"(_dim|_rank|hidden_size|intermediate_size|head_size|"
                   r"n_embd|n_inner|experts_per_tok|expansion)$")


class ContractError(ValueError):
    pass


def _need(ok, message):
    if not ok:
        raise ContractError(message)


def check_config(entry, cfg):
    """``entry`` (of ``configs`` in the manifest) against ``cfg`` (its
    file).  ``reduced`` is the same list in both, of the file's own keys
    whose values are smaller than the source's; where it is not empty the
    file says what the source has (``published``) and over how many chips a
    layer is shared, how, and what this chip holds (``deployment``)."""
    name = entry.get("name")
    _need(set(entry) == ENTRY_KEYS, f"{name}: entry keys {sorted(entry)}")
    _need(cfg.get("name") == name and cfg.get("source"),
          f"{name}: the file names {cfg.get('name')!r} and a source")
    _need(len(entry["source"]) <= 200 and len(entry["why"]) <= 200,
          f"{name}: source or why over 200 characters")
    reduced = entry["reduced"]
    _need(isinstance(reduced, list) and len(reduced) <= 16
          and len(set(reduced)) == len(reduced),
          f"{name}: reduced is a list of at most 16 distinct keys")
    _need(cfg.get("reduced") == reduced,
          f"{name}: reduced differs: entry {reduced}, file "
          f"{cfg.get('reduced')}")
    if not reduced:
        return
    published = cfg.get("published")
    _need(isinstance(published, dict) and set(published) == set(reduced),
          f"{name}: published gives the source's value of each reduced key "
          f"and of no other")
    for key in reduced:
        _need(not WIDTH.search(key), f"{name}: {key} is a width: never cut")
        _need(key in cfg, f"{name}: reduced key {key!r} is not in the file")
        held, source = cfg[key], published[key]
        _need(held != source,
              f"{name}: {key} is {held!r} here and in published: not cut")
        if isinstance(held, (int, float)) and \
                isinstance(source, (int, float)):
            _need(held < source, f"{name}: {key} {held} is not smaller than "
                  f"the source's {source}")
    deployment = cfg.get("deployment")
    _need(isinstance(deployment, str) and deployment.strip()
          and "\n" not in deployment,
          f"{name}: a cut configuration says its deployment in one line")


def stands_once_after(names, name, earlier):
    """``name`` is in the list once, after every one of ``earlier`` that
    the list holds: what a cell's test may say of a list's order.  A later
    PR appends its cell to the list, so nothing is said of what follows."""
    return names.count(name) == 1 and all(
        names.index(e) < names.index(name) for e in earlier if e in names)


def in_this_order(names, wanted):
    """The ``wanted`` names stand in ``names`` once each, in that order,
    wherever: other names may stand before, between and after them."""
    return [n for n in names if n in wanted] == list(wanted)

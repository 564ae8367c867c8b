"""The one general traffic generator.

A traffic mix is a data file `traffic/<mix>.json`:

  clients      connections in the closed loop
  session      statements every connection runs first (not timed)
  statements   how many statements to generate; client i starts at
               statement i * (statements // clients) and cycles
  templates    statement templates, taken in turn (statement j uses
               template j mod len); each has a `name`, the `sql` text with
               `{placeholders}`, the base `tables` it reads (their rows
               count towards a rows-per-second rate), its `params`, the
               `reference` module that judges its answers
               (`references/<name>.py`) and, for a roofline share, the
               `work` function that counts what it has to read (`work.py`)

A parameter is drawn from the seed for every statement:

  {"kind": "int", "lo": a, "hi": b}            a whole number in [a, b]
  {"kind": "choice", "values": [...]}          one of the values
  {"kind": "decimal", "lo": a, "hi": b, "scale": s,
   "offsets": {"name_lo": -1, "name_hi": 1}}   a scaled integer in [a, b],
        written with `s` decimals; each offset adds a further placeholder
        holding the value plus that many units
  {"kind": "pool", "pool": "queries"}          entry j of a pool of
        literals that the configuration's loader made from the seed

`{config.<key>}` in a template or a session statement is the value of that
key in the configuration file.  Nothing here knows a cell by name.
"""

import json
import os
import random
import re

HERE = os.path.dirname(os.path.abspath(__file__))
_CONFIG_KEY = re.compile(r"\{config\.([A-Za-z0-9_]+)\}")


def load_mix(name):
    with open(os.path.join(HERE, "traffic", name + ".json")) as f:
        return json.load(f)


def _decimal(units, scale):
    sign, units = ("-" if units < 0 else ""), abs(units)
    return f"{sign}{units // 10 ** scale}.{units % 10 ** scale:0{scale}d}"


def _config_values(text, config):
    return _CONFIG_KEY.sub(lambda m: str(config[m.group(1)]), text)


def generate(mix, config, pools, seed):
    """-> {"clients", "session", "statements": [sql], "starts": [int],
    "meta": [{"template", "params", "tables", "reference", "work"}]} — the
    same seed gives the same statements."""
    rng = random.Random(seed)
    templates = mix["templates"]
    n = int(mix["statements"])
    for t in templates:
        for spec in t.get("params", {}).values():
            if spec["kind"] == "pool":
                n = min(n, len(pools[spec["pool"]]))
    statements, meta = [], []
    for j in range(n):
        t = templates[j % len(templates)]
        values, params = {}, {}
        for name, spec in t.get("params", {}).items():
            if spec["kind"] == "int":
                params[name] = rng.randint(spec["lo"], spec["hi"])
                values[name] = str(params[name])
            elif spec["kind"] == "choice":
                params[name] = rng.choice(spec["values"])
                values[name] = str(params[name])
            elif spec["kind"] == "decimal":
                params[name] = rng.randint(spec["lo"], spec["hi"])
                values[name] = _decimal(params[name], spec["scale"])
                for extra, off in spec.get("offsets", {}).items():
                    values[extra] = _decimal(params[name] + off,
                                             spec["scale"])
            elif spec["kind"] == "pool":
                params[name] = j
                values[name] = pools[spec["pool"]][j]
            else:
                raise ValueError(f"unknown parameter kind {spec['kind']!r}")
        sql = _config_values(t["sql"], config)
        for name, text in values.items():
            sql = sql.replace("{" + name + "}", text)
        statements.append(" ".join(sql.split()))
        meta.append({"template": t["name"], "params": params,
                     "tables": t.get("tables", []),
                     "reference": t["reference"], "work": t.get("work")})
    clients = int(mix["clients"])
    return {"clients": clients,
            "session": [_config_values(s, config)
                        for s in mix.get("session", [])],
            "statements": statements,
            "starts": [i * (n // clients) for i in range(clients)],
            "meta": meta}

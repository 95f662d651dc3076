"""Upstream Kolibrie's employee data set, regenerated.

``kolibrie/benches/my_benchmark.rs`` reads
``synthetic_data_employee_100K.rdf``, an LFS pointer upstream, so the data is
made here with the shape its two queries need (and ``bench.py:60-80`` and
``benches/bench_subquery.py`` give it): per employee a ``foaf:name``, a
``foaf:title`` (one of ``titles``, "Developer" among them), a
``foaf:workplaceHomepage`` (one of ``companies``) and a ``ds:annual_salary``
(one of ``salary_steps`` values from 30000 up in steps of 1000).  The title,
the company and the salary of employee *i* are drawn from the seed.
"""

import numpy as np

FOAF = "http://xmlns.com/foaf/0.1/"
DS = "https://data.example/ontology#"


def generate(config: dict, seed: int, scale=None) -> dict:
    n = int(scale or config["employees"])
    n_comp, n_sal = int(config["companies"]), int(config["salary_steps"])
    rng = np.random.default_rng(int(seed))
    titles = list(config["titles"])
    company = rng.integers(0, n_comp, n)
    salary = rng.integers(0, n_sal, n)
    title = rng.integers(0, len(titles), n)

    preds = [f"<{FOAF}name>", f"<{FOAF}title>", f"<{FOAF}workplaceHomepage>",
             f"<{DS}annual_salary>"]
    company_iri = [f"https://company{c}.example/" for c in range(n_comp)]
    terms = list(preds)
    title0 = len(terms)
    terms += [f'"{t}"' for t in titles]
    comp0 = len(terms)
    terms += [f"<{c}>" for c in company_iri]
    sal0 = len(terms)
    terms += [f'"{30000 + k * 1000}"' for k in range(n_sal)]
    emp0 = len(terms)
    terms += [f"<https://data.example/employee/{i}>" for i in range(n)]
    name0 = len(terms)
    terms += [f'"Employee {i}"' for i in range(n)]

    emp = emp0 + np.arange(n, dtype=np.int64)
    # four triples per employee, employee-major like the source file
    s = np.repeat(emp, 4)
    p = np.tile(np.arange(4, dtype=np.int64), n)
    o = np.stack(
        [name0 + np.arange(n), title0 + title, comp0 + company, sal0 + salary],
        axis=1,
    ).ravel().astype(np.int64)
    return {"terms": terms, "s": s, "p": p, "o": o,
            "domains": {"company": company_iri}}

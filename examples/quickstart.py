"""Quickstart: the paper's running example (Examples 1-9) end to end.

    PYTHONPATH=src python examples/quickstart.py
"""
import numpy as np

from repro.compile_cache import enable_compile_cache
from repro.core import (
    Dictionary,
    InterestExpr,
    IrapEngine,
    StepCapacities,
    to_numpy,
)

A = "rdf:type"


def show(d, title, store_or_out):
    print(f"\n== {title} ==")
    for s, p, o in d.decode_triples(to_numpy(store_or_out)):
        print(f"  {s} {p} {o} .")


def main():
    enable_compile_cache()
    d = Dictionary()
    # Example 2: interest in athletes with goals, optionally a homepage
    expr = InterestExpr.parse(
        source="http://live.dbpedia.org/changesets",
        target="http://localhost:3030/target/sparql",
        bgp=[("?a", A, "dbo:Athlete"), ("?a", "dbp:goals", "?goals")],
        ogp=[("?a", "foaf:homepage", "?page")],
    )
    tau0 = d.encode_triples([
        ("dbr:Marcel", A, "dbo:Athlete"),
        ("dbr:Cristiano_Ronaldo", A, "dbo:Athlete"),
        ("dbr:Cristiano_Ronaldo", "dbp:goals", "96"),
        ("dbr:Cristiano_Ronaldo", "foaf:homepage", '"http://cristianoronaldo.com"'),
    ])
    engine = IrapEngine(d)
    sub = engine.register_interest(
        expr,
        StepCapacities(n_removed=16, n_added=16, tau=64, rho=64, pulls=64),
        initial_target=tau0,
    )

    # Example 1: the changeset
    removed = d.encode_triples([
        ("dbr:Marcel", "dbp:goals", "1"),
        ("dbr:Marcel", "dbo:team", "dbr:FNFT"),
        ("dbr:Tim%02", "foaf:name", '"Tim Berners-Lee"'),
        ("dbr:Cristiano_Ronaldo", "dbp:goals", "96"),
    ])
    added = d.encode_triples([
        ("dbr:Cristiano_Ronaldo", "dbp:goals", "216"),
        ("dbr:Barack_Obama", "foaf:name", '"Barack Obama"'),
        ("dbr:Barack_Obama", "foaf:homepage", '"http://www.barackobama.com/"'),
        ("dbr:Rio_Ferdinand", A, "foaf:Person"),
        ("dbr:Rio_Ferdinand", A, "dbo:Athlete"),
        ("dbr:Rio_Ferdinand", "dbp:goals", "10"),
        ("dbr:Arvid_Smit", A, "dbo:Athlete"),
    ])

    out = sub.apply(removed, added)
    show(d, "interesting removed  r  (Example 5)", out.r)
    show(d, "moved to ρ            r' (Example 5)", out.r_prime)
    show(d, "interesting added    a  (Example 6)", out.a)
    show(d, "potentially added    a_i (Example 6)", out.a_i)
    show(d, "resulting target τ   (Listing 1.3)", sub.tau)
    show(d, "potential dataset ρ  (Listing 1.4)", sub.rho)

    # a later changeset promotes Arvid out of ρ
    out2 = sub.apply(
        np.zeros((0, 3), np.int32),
        d.encode_triples([("dbr:Arvid_Smit", "dbp:goals", "3")]),
    )
    show(d, "second changeset: promoted adds", out2.a)
    show(d, "ρ after promotion", sub.rho)


if __name__ == "__main__":
    main()

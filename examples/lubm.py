"""Deterministic LUBM-style dataset generator.

The reference repo has no LUBM data (BASELINE.md: "LUBM data not in the
reference repo — generate with the standard LUBM generator"); this is a
self-contained, deterministic miniature with the same schema shape used by
LUBM queries Q2/Q9: universities, departments, faculty, students, courses,
and the predicates those queries join over.

``generate(n_universities)`` yields dictionary-encoded ID columns directly
(strings never materialized for the bulk of the data) — the TPU-native
ingest path.
"""

from typing import Dict, Tuple

import numpy as np

UB = "http://swat.cse.lehigh.edu/onto/univ-bench.owl#"
RDF_TYPE = "http://www.w3.org/1999/02/22-rdf-syntax-ns#type"

DEPTS_PER_UNIV = 8
PROFS_PER_DEPT = 12
STUDENTS_PER_DEPT = 80
GRAD_RATIO = 4  # every 4th student is a graduate student
COURSES_PER_DEPT = 15


# Knuth-style multiplicative hash constants for the degree-university pick —
# the SINGLE source of truth for both generators (tests assert the loop and
# vectorized generators emit identical triple sets).
_H_U, _H_D, _H_ST = 2654435761, 40503, 97


def _degree_univ(u, d, st, n_universities):
    """Deterministic pseudo-random university for a grad student's
    undergraduate degree.  Accepts scalars or numpy arrays (the vectorized
    generator broadcasts over (U, D, G))."""
    out = (
        np.uint64(_H_U) * np.asarray(u, np.uint64)
        + np.uint64(_H_D) * np.asarray(d, np.uint64)
        + np.uint64(_H_ST) * np.asarray(st, np.uint64)
    ) % np.uint64(n_universities)
    return int(out) if out.ndim == 0 else out.astype(np.int64)


def generate(
    n_universities: int, dictionary
) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Returns (s, p, o) uint32 columns for an n-university LUBM-like KG."""
    enc = dictionary.encode
    p_type = enc(RDF_TYPE)
    p_sub_org = enc(UB + "subOrganizationOf")
    p_member = enc(UB + "memberOf")
    p_works = enc(UB + "worksFor")
    p_advisor = enc(UB + "advisor")
    p_takes = enc(UB + "takesCourse")
    p_teaches = enc(UB + "teacherOf")
    p_degree = enc(UB + "undergraduateDegreeFrom")
    c_univ = enc(UB + "University")
    c_dept = enc(UB + "Department")
    c_prof = enc(UB + "FullProfessor")
    c_grad = enc(UB + "GraduateStudent")
    c_ugrad = enc(UB + "UndergraduateStudent")
    c_course = enc(UB + "Course")

    s, p, o = [], [], []

    def emit(subj, pred, obj):
        s.append(subj)
        p.append(pred)
        o.append(obj)

    for u in range(n_universities):
        univ = enc(f"http://www.University{u}.edu")
        emit(univ, p_type, c_univ)
        for d in range(DEPTS_PER_UNIV):
            dept = enc(f"http://www.Department{d}.University{u}.edu")
            emit(dept, p_type, c_dept)
            emit(dept, p_sub_org, univ)
            courses = []
            for c in range(COURSES_PER_DEPT):
                crs = enc(
                    f"http://www.Department{d}.University{u}.edu/Course{c}"
                )
                emit(crs, p_type, c_course)
                courses.append(crs)
            profs = []
            for f in range(PROFS_PER_DEPT):
                prof = enc(
                    f"http://www.Department{d}.University{u}.edu/FullProfessor{f}"
                )
                emit(prof, p_type, c_prof)
                emit(prof, p_works, dept)
                crs = courses[f % COURSES_PER_DEPT]
                emit(prof, p_teaches, crs)
                profs.append(prof)
            for st in range(STUDENTS_PER_DEPT):
                stu = enc(
                    f"http://www.Department{d}.University{u}.edu/Student{st}"
                )
                grad = st % GRAD_RATIO == 0
                emit(stu, p_type, c_grad if grad else c_ugrad)
                emit(stu, p_member, dept)
                advisor = profs[st % PROFS_PER_DEPT]
                emit(stu, p_advisor, advisor)
                # every student takes the course their advisor teaches plus
                # one other — Q9's triangle closes for the former
                emit(stu, p_takes, courses[st % PROFS_PER_DEPT])
                emit(stu, p_takes, courses[(st + 7) % COURSES_PER_DEPT])
                if grad:
                    # Q2's triangle: degree from the university owning the
                    # department the student is a member of (every 3rd), or
                    # a pseudo-random other university (deterministic hash,
                    # identical in the vectorized generator)
                    if st % 3 == 0:
                        emit(stu, p_degree, univ)
                    else:
                        other = _degree_univ(u, d, st, n_universities)
                        emit(
                            stu,
                            p_degree,
                            enc(f"http://www.University{other}.edu"),
                        )
    return (
        np.asarray(s, dtype=np.uint32),
        np.asarray(p, dtype=np.uint32),
        np.asarray(o, dtype=np.uint32),
    )


def generate_fast(
    n_universities: int, dictionary
) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Vectorized twin of :func:`generate` — IDENTICAL triple set (tested),
    built as numpy blocks instead of per-triple Python appends, so
    LUBM-1000-scale datasets (~3.8M triples) assemble in seconds.

    Entity IRIs are interned in contiguous blocks; all triple columns are
    assembled by repeat/tile/advanced-indexing over the entity ID arrays.
    """
    enc = dictionary.encode
    p_type = np.uint32(enc(RDF_TYPE))
    p_sub_org = np.uint32(enc(UB + "subOrganizationOf"))
    p_member = np.uint32(enc(UB + "memberOf"))
    p_advisor = np.uint32(enc(UB + "advisor"))
    p_works = np.uint32(enc(UB + "worksFor"))
    p_takes = np.uint32(enc(UB + "takesCourse"))
    p_teaches = np.uint32(enc(UB + "teacherOf"))
    p_degree = np.uint32(enc(UB + "undergraduateDegreeFrom"))
    c_univ = np.uint32(enc(UB + "University"))
    c_dept = np.uint32(enc(UB + "Department"))
    c_prof = np.uint32(enc(UB + "FullProfessor"))
    c_grad = np.uint32(enc(UB + "GraduateStudent"))
    c_ugrad = np.uint32(enc(UB + "UndergraduateStudent"))
    c_course = np.uint32(enc(UB + "Course"))

    U, D, C, F, S = (
        n_universities,
        DEPTS_PER_UNIV,
        COURSES_PER_DEPT,
        PROFS_PER_DEPT,
        STUDENTS_PER_DEPT,
    )

    def intern(strings) -> np.ndarray:
        return np.fromiter(
            (enc(s) for s in strings), dtype=np.uint32, count=len(strings)
        )

    univ = intern([f"http://www.University{u}.edu" for u in range(U)])
    depts = [f"http://www.Department{d}.University{u}.edu"
             for u in range(U) for d in range(D)]
    dept = intern(depts).reshape(U, D)
    course = intern(
        [f"{dd}/Course{c}" for dd in depts for c in range(C)]
    ).reshape(U, D, C)
    prof = intern(
        [f"{dd}/FullProfessor{f}" for dd in depts for f in range(F)]
    ).reshape(U, D, F)
    stu = intern(
        [f"{dd}/Student{st}" for dd in depts for st in range(S)]
    ).reshape(U, D, S)

    st_idx = np.arange(S)
    grad_mask = st_idx % GRAD_RATIO == 0

    blocks = []  # (s, p, o) uint32 arrays

    def block(s, p, o):
        s = np.asarray(s, dtype=np.uint32).ravel()
        o = np.asarray(o, dtype=np.uint32).ravel()
        blocks.append((s, np.full(len(s), p, dtype=np.uint32), o))

    block(univ, p_type, np.full(U, c_univ))
    block(dept, p_type, np.full(U * D, c_dept))
    block(dept, p_sub_org, np.repeat(univ, D))
    block(course, p_type, np.full(U * D * C, c_course))
    block(prof, p_type, np.full(U * D * F, c_prof))
    block(prof, p_works, np.repeat(dept.ravel(), F))
    block(prof, p_teaches, course[:, :, :F])  # prof f teaches course f
    block(
        stu,
        p_type,
        np.where(grad_mask, c_grad, c_ugrad)[None, None, :].repeat(U, 0).repeat(D, 1),
    )
    block(stu, p_member, np.repeat(dept.ravel(), S))
    block(stu, p_advisor, prof[:, :, st_idx % F])
    block(stu, p_takes, course[:, :, st_idx % F])
    block(stu, p_takes, course[:, :, (st_idx + 7) % C])
    # degrees: every grad; own university when st % 3 == 0, else the shared
    # deterministic hash pick (see _degree_univ)
    g_st = st_idx[grad_mask]  # (G,)
    own = g_st % 3 == 0
    other = _degree_univ(
        np.arange(U)[:, None, None],
        np.arange(D)[None, :, None],
        g_st[None, None, :],
        U,
    )  # (U, D, G)
    deg_univ = univ[other]  # (U, D, G)
    # own-university rows overwrite the hash pick
    deg_univ[:, :, own] = np.broadcast_to(
        univ[:, None, None], (U, D, int(own.sum()))
    )
    block(stu[:, :, grad_mask], p_degree, deg_univ)

    s = np.concatenate([b[0] for b in blocks])
    p = np.concatenate([b[1] for b in blocks])
    o = np.concatenate([b[2] for b in blocks])
    return s, p, o


def predicate_ids(dictionary) -> Dict[str, int]:
    return {
        name: dictionary.encode(UB + name)
        for name in (
            "subOrganizationOf",
            "memberOf",
            "worksFor",
            "advisor",
            "takesCourse",
            "teacherOf",
            "undergraduateDegreeFrom",
        )
    }


LUBM_Q2 = """PREFIX rdf: <http://www.w3.org/1999/02/22-rdf-syntax-ns#>
PREFIX ub: <http://swat.cse.lehigh.edu/onto/univ-bench.owl#>
SELECT ?x ?y ?z WHERE {
    ?x rdf:type ub:GraduateStudent .
    ?y rdf:type ub:University .
    ?z rdf:type ub:Department .
    ?x ub:memberOf ?z .
    ?z ub:subOrganizationOf ?y .
    ?x ub:undergraduateDegreeFrom ?y
}"""

LUBM_Q9 = """PREFIX rdf: <http://www.w3.org/1999/02/22-rdf-syntax-ns#>
PREFIX ub: <http://swat.cse.lehigh.edu/onto/univ-bench.owl#>
SELECT ?x ?y ?z WHERE {
    ?x ub:advisor ?y .
    ?y ub:teacherOf ?z .
    ?x ub:takesCourse ?z
}"""

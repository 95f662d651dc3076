"""LUBM data in the shape of UBA, the benchmark's own generator (Guo, Pan,
Heflin 2005, section 2.2 and UBA 1.7's ``Generator.java``), from ``--seed``.

Per university 15-25 departments; per department 7-10 full, 10-14 associate
and 8-11 assistant professors and 5-7 lecturers; undergraduates 8-14 and
graduates 3-4 to a member of the faculty; every member of the faculty teaches
1-2 courses and 1-2 graduate courses, holds three degrees from any of 1000
universities and has 15-20 / 10-18 / 5-10 / 0-5 publications by rank; an
undergraduate takes 2-4 courses and one in five has a professor as advisor; a
graduate takes 1-3 graduate courses, has an advisor and an undergraduate
degree, co-authors 0-5 of the advisor's publications, and a fifth to a quarter
are teaching assistants, a quarter to a third research assistants; 10-20
research groups; names, e-mail addresses, telephones and research interests
as literals.  About 10^5 triples a university, as UBA's (LUBM(1,0): 103,397).

Not UBA's stream of random numbers (that is Java's), so LUBM(N, seed) here is
the same distribution and not the same file; courses are numbered in the order
of their teachers (UBA draws their numbers from a pool of 100); the
universities' department counts are the N evenly spaced values of 15-25 in an
order drawn from the seed (UBA draws each at random), so that every seed's
data set is of one size.  What a store
without a reasoner needs is materialised, as the configuration's ``assumed``
says: ``rdf:type`` up univ-bench's class hierarchy to Professor, Faculty,
Student, Course and Person.
"""

import numpy as np

UB = "http://swat.cse.lehigh.edu/onto/univ-bench.owl#"
RDF_TYPE = "http://www.w3.org/1999/02/22-rdf-syntax-ns#type"
DEGREE_UNIVERSITIES = 1000  # UBA's UNIV_NUM: degrees come from any of these
RESEARCH_AREAS = 30
# rank: (members min, max, publications min, max, is a professor)
RANKS = {
    "FullProfessor": (7, 10, 15, 20, True),
    "AssociateProfessor": (10, 14, 10, 18, True),
    "AssistantProfessor": (8, 11, 5, 10, True),
    "Lecturer": (5, 7, 0, 5, False),
}
SUPERCLASSES = {
    "FullProfessor": ("Professor", "Faculty", "Person"),
    "AssociateProfessor": ("Professor", "Faculty", "Person"),
    "AssistantProfessor": ("Professor", "Faculty", "Person"),
    "Lecturer": ("Faculty", "Person"),
    "UndergraduateStudent": ("Student", "Person"),
    "GraduateStudent": ("Student", "Person"),
    "GraduateCourse": ("Course",),
}
PREDICATES = (
    "name", "subOrganizationOf", "worksFor", "headOf", "memberOf", "teacherOf",
    "takesCourse", "advisor", "teachingAssistantOf", "publicationAuthor",
    "undergraduateDegreeFrom", "mastersDegreeFrom", "doctoralDegreeFrom",
    "emailAddress", "telephone", "researchInterest")
CLASSES = (
    "University", "Department", "ResearchGroup", "Publication", "Course",
    "GraduateCourse", "FullProfessor", "AssociateProfessor",
    "AssistantProfessor", "Lecturer", "Professor", "Faculty",
    "UndergraduateStudent", "GraduateStudent", "Student", "Person",
    "TeachingAssistant", "ResearchAssistant")


class _Store:
    """Term table and triple blocks."""

    def __init__(self):
        self.terms, self.index, self.blocks = [], {}, []

    def ids(self, terms) -> np.ndarray:
        out = np.empty(len(terms), np.int64)
        index, table = self.index, self.terms
        for k, term in enumerate(terms):
            i = index.get(term)
            if i is None:
                i = index[term] = len(table)
                table.append(term)
            out[k] = i
        return out

    def iris(self, texts) -> np.ndarray:
        return self.ids([f"<{t}>" for t in texts])

    def literals(self, texts) -> np.ndarray:
        return self.ids([f'"{t}"' for t in texts])

    def add(self, s, p, o) -> None:
        s = np.asarray(s, np.int64).ravel()
        o = np.broadcast_to(np.asarray(o, np.int64).ravel(), s.shape)
        self.blocks.append((s, np.full(len(s), p, np.int64), o))


def _distinct_picks(rng, rows, pool, lo, hi):
    """For each of ``rows`` rows, ``lo..hi`` distinct picks from ``pool``
    values: ``(row index, picked value)`` columns."""
    hi = min(hi, pool)
    order = rng.random((rows, pool)).argsort(axis=1)[:, :hi]
    count = rng.integers(min(lo, hi), hi + 1, rows)
    keep = np.arange(hi)[None, :] < count[:, None]
    return np.nonzero(keep)[0], order[keep]


def generate(config: dict, seed: int, scale=None) -> dict:
    """``{"terms", "s", "p", "o", "domains"}``: N-Triples terms, id columns
    and the constants a traffic file may draw."""
    universities = int(scale or config["universities"])
    rng = np.random.default_rng([int(seed), 2005])
    st = _Store()
    p_type = st.iris([RDF_TYPE])[0]
    pred = dict(zip(PREDICATES, st.iris([UB + n for n in PREDICATES])))
    cls = dict(zip(CLASSES, st.iris([UB + n for n in CLASSES])))
    telephone = st.literals(["xxx-xxx-xxxx"])[0]
    research = st.literals([f"Research{k}" for k in range(RESEARCH_AREAS)])
    degree_univ = st.iris(
        [f"http://www.University{k}.edu" for k in range(DEGREE_UNIVERSITIES)])
    degree_seen = np.zeros(DEGREE_UNIVERSITIES, bool)

    def typed(subjects, name):
        for c in (name,) + SUPERCLASSES.get(name, ()):
            st.add(subjects, p_type, cls[c])

    def degrees(subjects, predicate):
        picks = rng.integers(0, DEGREE_UNIVERSITIES, len(subjects))
        degree_seen[picks] = True
        st.add(subjects, pred[predicate], degree_univ[picks])

    def people(base, host, names):
        """Name, e-mail and telephone of a department's people."""
        who = st.iris([f"{base}/{n}" for n in names])
        st.add(who, pred["name"], st.literals(names))
        st.add(who, pred["emailAddress"], st.literals([f"{n}@{host}" for n in names]))
        st.add(who, pred["telephone"], telephone)
        return who

    # 15-25 departments a university, as UBA's, but stratified: every seed
    # gets the same department counts in another order, so that the size of
    # the data set -- the work of a run -- does not change with the seed
    n_depts = rng.permutation(
        15 + ((np.arange(universities) + 0.5) * 11 / universities).astype(int))
    univ_iri, dept_iri = [], []
    for u in range(universities):
        univ_iri.append(f"http://www.University{u}.edu")
        univ = degree_univ[u]
        degree_seen[u] = True
        st.add([univ], pred["name"], st.literals([f"University{u}"]))
        for d in range(int(n_depts[u])):
            host = f"Department{d}.University{u}.edu"
            base = "http://www." + host
            dept_iri.append(base)
            dept = st.iris([base])[0]
            typed([dept], "Department")
            st.add([dept], pred["name"], st.literals([f"Department{d}"]))
            st.add([dept], pred["subOrganizationOf"], univ)

            # ---- the faculty, rank by rank
            names, rank_of, pubs_of, professor = [], [], [], []
            for rank, (lo, hi, pub_lo, pub_hi, is_prof) in RANKS.items():
                n = int(rng.integers(lo, hi + 1))
                names += [f"{rank}{i}" for i in range(n)]
                rank_of += [rank] * n
                pubs_of.append(rng.integers(pub_lo, pub_hi + 1, n))
                professor += [is_prof] * n
            faculty = people(base, host, names)
            rank_of = np.array(rank_of)
            for rank in RANKS:
                typed(faculty[rank_of == rank], rank)
            st.add(faculty, pred["worksFor"], dept)
            st.add(faculty, pred["researchInterest"],
                   research[rng.integers(0, RESEARCH_AREAS, len(faculty))])
            for which in ("undergraduateDegreeFrom", "mastersDegreeFrom",
                          "doctoralDegreeFrom"):
                degrees(faculty, which)
            full = np.flatnonzero(rank_of == "FullProfessor")
            st.add([faculty[rng.choice(full)]], pred["headOf"], dept)
            professors = faculty[np.array(professor)]

            # ---- courses, each taught by one member of the faculty
            taught = {}
            for kind in ("Course", "GraduateCourse"):
                teacher = np.repeat(np.arange(len(faculty)),
                                    rng.integers(1, 3, len(faculty)))
                labels = [f"{kind}{i}" for i in range(len(teacher))]
                courses = st.iris([f"{base}/{n}" for n in labels])
                typed(courses, kind)
                st.add(courses, pred["name"], st.literals(labels))
                st.add(faculty[teacher], pred["teacherOf"], courses)
                taught[kind] = courses

            # ---- publications of the faculty
            pubs_of = np.concatenate(pubs_of)
            author = np.repeat(np.arange(len(faculty)), pubs_of)
            number = np.arange(len(author)) - np.repeat(
                np.cumsum(pubs_of) - pubs_of, pubs_of)
            labels = [f"Publication{k}" for k in number]
            pubs = st.iris([f"{base}/{names[a]}/{n}" for a, n in zip(author, labels)])
            typed(pubs, "Publication")
            st.add(pubs, pred["name"], st.literals(labels))
            st.add(pubs, pred["publicationAuthor"], faculty[author])
            first_pub = np.cumsum(pubs_of) - pubs_of

            # ---- undergraduates
            n = int(rng.integers(8 * len(faculty), 14 * len(faculty) + 1))
            under = people(base, host,
                           [f"UndergraduateStudent{i}" for i in range(n)])
            typed(under, "UndergraduateStudent")
            st.add(under, pred["memberOf"], dept)
            who, what = _distinct_picks(rng, n, len(taught["Course"]), 2, 4)
            st.add(under[who], pred["takesCourse"], taught["Course"][what])
            advised = under[rng.integers(0, 5, n) == 0]
            st.add(advised, pred["advisor"],
                   professors[rng.integers(0, len(professors), len(advised))])

            # ---- graduates
            n = int(rng.integers(3 * len(faculty), 4 * len(faculty) + 1))
            grad = people(base, host, [f"GraduateStudent{i}" for i in range(n)])
            typed(grad, "GraduateStudent")
            st.add(grad, pred["memberOf"], dept)
            degrees(grad, "undergraduateDegreeFrom")
            who, what = _distinct_picks(rng, n, len(taught["GraduateCourse"]), 1, 3)
            st.add(grad[who], pred["takesCourse"], taught["GraduateCourse"][what])
            advisor = rng.integers(0, len(professors), n)
            st.add(grad, pred["advisor"], professors[advisor])
            assistants = rng.permutation(n)[:int(rng.integers(n // 5, n // 4 + 1))]
            assistants = assistants[:len(taught["Course"])]
            typed(grad[assistants], "TeachingAssistant")
            st.add(grad[assistants], pred["teachingAssistantOf"],
                   taught["Course"][rng.permutation(len(taught["Course"]))
                                    [:len(assistants)]])
            typed(grad[rng.permutation(n)[:int(rng.integers(n // 4, n // 3 + 1))]],
                  "ResearchAssistant")
            # co-authors of 0-5 of the advisor's publications
            adv_fac = np.flatnonzero(np.array(professor))[advisor]
            shared = np.minimum(rng.integers(0, 6, n), pubs_of[adv_fac])
            who = np.repeat(np.arange(n), shared)
            nth = np.arange(len(who)) - np.repeat(np.cumsum(shared) - shared, shared)
            nth = (nth + np.repeat(rng.integers(0, 1000, n), shared)) % pubs_of[adv_fac[who]]
            st.add(pubs[first_pub[adv_fac[who]] + nth], pred["publicationAuthor"],
                   grad[who])

            groups = st.iris([f"{base}/ResearchGroup{i}"
                              for i in range(int(rng.integers(10, 21)))])
            typed(groups, "ResearchGroup")
            st.add(groups, pred["subOrganizationOf"], dept)

    # UBA types a university wherever it names one
    typed(degree_univ[degree_seen], "University")
    return {
        "terms": st.terms,
        "s": np.concatenate([b[0] for b in st.blocks]),
        "p": np.concatenate([b[1] for b in st.blocks]),
        "o": np.concatenate([b[2] for b in st.blocks]),
        "domains": {"university": univ_iri, "department": dept_iri},
    }
